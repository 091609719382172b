"""Closed-loop serving: a fixed number of requests is outstanding, and
each answer sends the next, as a batch job does (e.g. generating a
data set).  The window closes at the first cohort that ends after
`--seconds`; the rate is every answer over all of that time.

End-to-end: `serve_throughput`, answered requests per second.
"""
from __future__ import annotations

import collections
import time

from bench import harness, serving, traffic

MAX_IDS = 1 << 22   # ids past this are served but not sampled


def run(ctx: harness.Context) -> harness.Outcome:
    tr = ctx.traffic
    s = serving.build(ctx)
    order = traffic.payload_order(tr, ctx.seed, MAX_IDS)
    keep = serving.sample_mask(ctx.seed, MAX_IDS, ctx.cell["check"]["share"])
    outstanding = int(tr["outstanding"])
    backlog = collections.deque(range(outstanding))
    sent = outstanding
    submitted = answered = 0
    setup_s = ctx.setup_s()
    with harness.profiled(ctx.trace, ctx.compiles) as prof:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < ctx.seconds:
            ids = [backlog.popleft() for _ in range(s.slot_batch)]
            submitted += len(ids)
            got = serving.serve_cohort(s, ids, order, keep)
            answered += len(got)
            for _ in ids:   # each client sends its next request
                backlog.append(sent % MAX_IDS)
                sent += 1
        t_end = time.perf_counter() - t0
    mem = harness.memory_peak_bytes(ctx.devices)
    work = serving.work(ctx, s, answered)
    work["answered"] = answered
    serving.release(s)
    errs = serving.check_answers(
        ctx, s.kept, serving.payload_fn(s.pool, order))
    failed = submitted - answered
    checks = serving.checks(ctx, errs, failed)
    return harness.Outcome(
        attempted=submitted, failed=failed,
        end_to_end={"serve_throughput": answered / t_end,
                    "setup_s": setup_s},
        checks=checks,
        correct=serving.verdict(checks),
        memory_peak_bytes=mem, window_s=t_end, layer=work,
        trace=prof.view)
