"""What the serving drivers share: building the engine from the cell,
serving one cohort per `run()` call, and checking the sampled answers
against the reference once the window has closed.

`ConvServeEngine.run()` drains its whole queue and stamps nothing per
cohort, so the drivers keep the waiting requests themselves and hand
the engine one slot batch at a time: each `run()` call is one launch,
and its return is when that cohort's answers are on the host.  The
waiting line is bounded by the cell's `queue_limit`, as the engine's
own queue would be.
"""
from __future__ import annotations

import dataclasses
import gc
import math
import sys
import time
from typing import Dict, List, Sequence

import jax
import numpy as np

from bench import flops, harness, reference


@dataclasses.dataclass
class Served:
    """An engine built for a cell, its payloads and its bookkeeping."""
    engine: object
    kind: str
    pool: np.ndarray
    slot_batch: int
    queue_limit: int
    launch_s: List[float] = dataclasses.field(default_factory=list)
    kept: Dict[int, np.ndarray] = dataclasses.field(default_factory=dict)


def build(ctx: harness.Context) -> Served:
    """Weights and payloads from the seed, the engine over them, and every
    shape the traffic uses compiled and run once."""
    from repro.serve.conv_engine import ConvRequest, ConvServeEngine
    model, eng_cfg = ctx.config["model"], ctx.cell["engine"]
    phase = _phases(ctx)
    mod = harness.model_of(ctx)
    params = mod.params(model, ctx.seed)
    phase("weights")
    engine = ConvServeEngine(slot_batch=eng_cfg["slot_batch"],
                             queue_limit=eng_cfg["queue_limit"],
                             ladder=tuple(eng_cfg["ladder"]),
                             **mod.engine_kwargs(model, params))
    kind = mod.REQUEST_KIND
    engine.warmup([(kind, mod.payload_shape(model))], compile=True)
    phase("engine warm-up")
    pool = np.asarray(mod.payload_pool(
        model, ctx.seed, int(ctx.traffic["payload_pool"])))
    phase("payloads")
    # One real launch through the engine's whole host path.
    for i in range(engine.slot_batch):
        engine.submit(ConvRequest(uid=i, kind=kind,
                                  payload=pool[i % len(pool)]))
    engine.run()
    engine.stats.update(submitted=0, completed=0, launches=0, h2d_bytes=0)
    phase("first launch")
    return Served(engine=engine, kind=kind, pool=pool,
                  slot_batch=engine.slot_batch,
                  queue_limit=int(eng_cfg["queue_limit"]))


def _phases(ctx: harness.Context):
    """Print, on standard error, how long each step of set-up took."""
    last = [0.0, {}]

    def phase(name: str) -> None:
        now, seen = ctx.setup_s(), dict(ctx.compiles)
        new = {k: round(v - last[1].get(k, 0), 3) for k, v in seen.items()
               if v != last[1].get(k, 0)}
        print(f"setup: {name} {now - last[0]:.3f} s {new}", file=sys.stderr)
        last[:] = [now, seen]
    phase("start-up")
    return phase


def serve_cohort(s: Served, ids: Sequence[int], order: np.ndarray,
                 keep: np.ndarray) -> Dict[int, float]:
    """Launch one cohort; returns {id: host time its answer arrived} for
    the answered ones and keeps the sampled answers."""
    from repro.serve.conv_engine import ConvRequest
    for j in ids:
        s.engine.submit(ConvRequest(uid=j, kind=s.kind,
                                    payload=s.pool[order[j]]))
    a = time.perf_counter()
    res = s.engine.run()
    b = time.perf_counter()
    s.launch_s.append(b - a)
    for j, out in res.items():
        if j < len(keep) and keep[j]:
            s.kept[j] = out
    return {j: b for j in res}


def sample_mask(seed: int, n: int, share: float) -> np.ndarray:
    """Which request ids have their answers compared: drawn from the
    seed, about `share` of them."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 3]))
    return rng.random(n) < share


def release(s: Served) -> None:
    """Free the engine's weights and compiled programs before the
    reference runs, so that the reference cannot set the memory peak."""
    s.engine = None
    gc.collect()


def serve_forward(model: Dict, mod, num: reference.Numerics):
    """`(params, batch) -> outputs` of one served launch, jitted: the
    kind's reference forward at the numerics `num`."""
    return jax.jit(lambda p, b: mod.forward(model, p, b, num))


def check_answers(ctx: harness.Context, kept: Dict[int, np.ndarray],
                  payloads) -> Dict[str, float]:
    """The kept answers against the reference, worst request first:
    `out_err`, max |answer - reference| / max |reference|, and
    `out_rms_err`, |answer - reference|_2 / |reference|_2.  `payloads(ids)`
    gives the requests' inputs.  The reference makes its weights again
    from the seed and runs in blocks of the engine's slot batch."""
    model, mod = ctx.config["model"], harness.model_of(ctx)
    params = mod.params(model, ctx.seed)
    fwd = serve_forward(model, mod, reference.numerics(ctx.config))
    ids = sorted(kept)
    block = int(ctx.cell["engine"]["slot_batch"])
    worst = {"out_err": 0.0, "out_rms_err": 0.0}
    for k in range(0, len(ids), block):
        part = ids[k:k + block]
        x = payloads(part)
        pad = block - len(part)
        if pad:
            x = np.concatenate([x, np.zeros((pad,) + x.shape[1:], x.dtype)])
        ref = np.asarray(fwd(params, x), np.float64)[:len(part)]
        for r, j in zip(ref, part):
            got = np.asarray(kept[j], np.float64)
            if got.shape != r.shape or not np.all(np.isfinite(got)):
                return {k: math.inf for k in worst}
            d = got - r
            worst["out_err"] = max(worst["out_err"], float(
                np.max(np.abs(d)) / np.max(np.abs(r))))
            worst["out_rms_err"] = max(worst["out_rms_err"], float(
                np.linalg.norm(d) / np.linalg.norm(r)))
    return worst


def control_error(ctx: harness.Context, ids: Sequence[int],
                  payloads) -> Dict[str, float]:
    """The control's reading: the reference computed in bfloat16, put in
    the program's place for requests `ids`, held to the same comparison
    as a run's answers."""
    model, mod = ctx.config["model"], harness.model_of(ctx)
    params = mod.params(model, ctx.seed)
    low = serve_forward(model, mod,
                        reference.numerics(ctx.config, control=True))
    block = int(ctx.cell["engine"]["slot_batch"])
    kept = {}
    for k in range(0, len(ids), block):
        part = list(ids[k:k + block])
        out = np.asarray(low(params, payloads(part)))
        kept.update(zip(part, out))
    return check_answers(ctx, kept, payloads)


def checks(ctx: harness.Context, errs: Dict[str, float], failed: int
           ) -> Dict[str, tuple]:
    """Each compared number beside the cell's limit for it: those of
    `errs` that the cell's `check` gives a limit, and the unanswered
    count."""
    lim = ctx.cell["check"]
    out = {k: (v, float(lim[k])) for k, v in errs.items() if k in lim}
    out["unanswered"] = (failed, 0)
    return out


def verdict(checks: Dict[str, tuple]) -> bool:
    """`correct`: every compared number within its limit."""
    return all(v <= lim for v, lim in checks.values())


def work(ctx: harness.Context, s: Served, completed: int) -> Dict:
    """What the readers need about the work done: useful FLOPs of the
    completed requests, and the roofline time and count of the Pallas
    launches the window made (each launch runs a full slot batch)."""
    model, mod = ctx.config["model"], harness.model_of(ctx)
    per_req = flops.total_flops(mod.ops(model, 1))
    launch_ops = mod.ops(model, s.slot_batch)
    pallas = [o for o in launch_ops if o.kernel == "pallas"]
    launches = len(s.launch_s)
    return {"useful_flops": per_req * completed,
            "launches": launches,
            "launch_host_s": float(sum(s.launch_s)),
            "pallas_per_launch": len(pallas),
            "pallas_roofline_s": launches * flops.roofline_s(pallas,
                                                              ctx.peaks),
            "slot_batch": s.slot_batch,
            "chips": len(ctx.devices),
            "peak_flops": ctx.peaks["flops_per_s"],
            "stats": {k: v for k, v in s.engine.stats.items()
                      if isinstance(v, int)} if s.engine else {}}


def payload_fn(s_pool: np.ndarray, order: np.ndarray):
    return lambda ids: np.stack([s_pool[order[j]] for j in ids])


def p95(values: np.ndarray) -> float:
    """Nearest-rank 95th percentile."""
    v = np.sort(np.asarray(values, np.float64))
    return float(v[max(0, int(np.ceil(0.95 * len(v))) - 1)])

