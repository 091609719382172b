"""Open-loop serving: requests fall due on the traffic's schedule whatever
the engine does, and each is timed from its due time to its answer on
the host.  A request still unanswered a minute after the window closed,
or turned away by a full waiting line, counts as failed, and its latency
as the time it waited until the run gave up on it.

End-to-end: `serve_p95_ms` over every request due in the window.
"""
from __future__ import annotations

import collections
import time
from typing import Dict

import numpy as np

from bench import harness, serving, traffic

DRAIN_S = 60.0


def window(s: serving.Served, due: np.ndarray, order: np.ndarray,
           keep: np.ndarray, seconds: float) -> Dict:
    """Serve every request of the schedule `due`; returns when each was
    answered (NaN if never), the turned-away count, the deepest waiting
    line and the window's length."""
    n = len(due)
    done = np.full(n, np.nan)
    backlog = collections.deque()
    shed = deepest = i = 0
    t0 = time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        while i < n and due[i] <= now:
            if len(backlog) >= s.queue_limit:
                shed += 1
            else:
                backlog.append(i)
            i += 1
        deepest = max(deepest, len(backlog))
        if backlog:
            ids = [backlog.popleft()
                   for _ in range(min(s.slot_batch, len(backlog)))]
            for j, t in serving.serve_cohort(s, ids, order, keep).items():
                done[j] = t - t0
        elif i < n:
            time.sleep(max(0.0, due[i] - (time.perf_counter() - t0)))
        else:
            break
        if now > seconds + DRAIN_S:
            break
    t_end = time.perf_counter() - t0
    answered = np.isfinite(done)
    return {"latency_s": np.where(answered, done, t_end) - due,
            "answered": int(answered.sum()), "sheds": shed,
            "deepest": deepest, "t_end": t_end}


def run(ctx: harness.Context) -> harness.Outcome:
    tr = ctx.traffic
    s = serving.build(ctx)
    due = traffic.open_schedule(tr, ctx.seed, ctx.seconds)
    n = len(due)
    order = traffic.payload_order(tr, ctx.seed, n)
    keep = serving.sample_mask(ctx.seed, n, ctx.cell["check"]["share"])
    setup_s = ctx.setup_s()
    with harness.profiled(ctx.trace, ctx.compiles) as prof:
        w = window(s, due, order, keep, ctx.seconds)
    mem = harness.memory_peak_bytes(ctx.devices)
    work = serving.work(ctx, s, w["answered"])
    work.update(answered=w["answered"], sheds=w["sheds"],
                deepest=w["deepest"])
    serving.release(s)
    errs = serving.check_answers(ctx, s.kept,
                                 serving.payload_fn(s.pool, order))
    failed = n - w["answered"]
    checks = serving.checks(ctx, errs, failed)
    return harness.Outcome(
        attempted=n, failed=failed,
        end_to_end={"serve_p95_ms": serving.p95(w["latency_s"]) * 1e3,
                    "setup_s": setup_s},
        checks=checks,
        correct=serving.verdict(checks),
        memory_peak_bytes=mem, window_s=w["t_end"], layer=work,
        trace=prof.view)
