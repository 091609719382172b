"""Fault-tolerant continuous-batching serving for the conv workloads.

`serve/engine.py` serves the LM; this engine serves what the paper is
actually about -- GAN generation and atrous segmentation on small
low-power accelerators (the HUGE2 edge regime, PAPERS.md).  In that
regime the engine must keep answering when a kernel path misbehaves,
not merely run fast on the happy path, so the robustness layer is the
core of the design (DESIGN.md Sec. 2.11):

  * **Geometry buckets.**  Each request's payload shape normalizes --
    through the models' `*_plan_requests` helpers, i.e. through
    `ConvSpec.make` -- into a bucket keyed by (workload kind, payload
    shape).  Each bucket owns compile-once jitted launch functions at a
    fixed slot batch, so serving never recompiles per request.  A bucket
    of large payloads (`DEVICE_SLOT_MIN_BYTES`) puts each request's
    payload on the device by itself and makes its empty slots there;
    smaller payloads cross as one zero-padded host slot batch.
  * **Bounded admission.**  Requests enter a bounded queue; submission
    beyond the bound is SHED (counted, rejected) rather than buffered
    without limit -- the engine can fall behind, it can never hang on an
    unbounded backlog.  Slots refill from the queue every launch.
  * **Degradation ladder.**  Per bucket, launches walk
    ``pallas -> xla_zero_free -> reference``.  A rung that raises (or
    NaNs twice) degrades the REQUEST to the next rung immediately, and
    feeds a per-(bucket, rung) circuit breaker: enough consecutive
    failures quarantine the rung (OPEN) so later launches skip it; after
    a cooldown the breaker half-opens and the next launch re-probes the
    rung, closing it again on success.  Eager fallback across rungs for
    everyone else lives in `core/spec.py::fallback_backend`; the engine
    drives its ladder explicitly because it needs breaker state and
    per-attempt stats around every rung.
  * **Deadlines, retries, NaN guard.**  Requests may carry a relative
    deadline: expired requests are dropped at dequeue and counted at
    completion.  Failed attempts back off exponentially (bounded); a
    non-finite output is retried once on the same rung (transient) and
    then degrades (systematic).
  * **Warmup.**  `warmup()` pre-plans `plan_strategy` tiles for every
    launch a bucket will make from a shipped `ECOFLOW_TILE_CACHE`
    artifact (`kernels.tiling.warmup_plans` -- artifact rows replayed,
    corrupt artifacts warned about and re-planned analytically, never an
    autotune sweep) and optionally pre-compiles the primary rung.

Fault injection (`serve/faults.py`) hooks the launch path OUTSIDE jit:
launch-class events fire before the jitted call, output-class events
poison the host-materialized result.  With no injector attached the
fast path is a plain jitted `generator_apply` / `atrous_head_apply`
with `backend="pallas"` -- exactly ONE forward `pallas_call` per conv
layer, same as the training stack (the jaxpr pins hold unmodified).

Tracing.  The serving loop writes `jax.profiler.TraceAnnotation` spans,
one set per cohort (never per request), into the profiler's own trace,
on the device trace's clock; with no profiler running they record
nothing.  `engine.cohort` is one iteration of `run()`'s loop and
holds `engine.take` (`_take_cohort`), `engine.launch` (the whole ladder;
args `kind`, `n`, `uid0` = the uid of the cohort's first request) and
`engine.answer` (deadline check, latency record, results).
`engine.launch` holds `engine.batch` (the slot batch: its payload copies,
or on the device-slot path the payloads' puts and the zero slots) and,
per attempt, `engine.dispatch` (injector hook and the
jitted call; args `rung`, `attempt`), `engine.fetch` (`np.asarray` of
the output) and `engine.check` (the host `isfinite`).  Building the
first engine also registers a `python.gc` span around every collection
of Python's cyclic garbage collector (arg `generation`).
"""
from __future__ import annotations

import dataclasses
import gc
import math
import time
from collections import deque
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.serve.faults import FaultInjector

DEFAULT_LADDER = ("pallas", "xla_zero_free", "reference")

KINDS = ("gan_gen", "aspp")

# `health()`'s latency percentiles cover this many most recent answers.
LATENCY_WINDOW = 10_000

# A bucket whose payload holds at least this many bytes puts each filled
# slot on the device by itself and makes its empty slots there
# (`_slots`), so no zero byte crosses the host link; smaller payloads
# cross as one host slot batch, since one transfer of a few KB beats a
# slot batch of tiny ones.  Set far from both payloads served here: a
# GAN latent (512 B at z_dim 128) and a DeepLabv3 block-4 feature map
# (33x33x2048 float32, 8.9 MB), so its exact value routes neither.
DEVICE_SLOT_MIN_BYTES = 1 << 20


@dataclasses.dataclass
class ConvRequest:
    """One inference request.

    kind       -- "gan_gen" (payload: a (z_dim,) latent) or "aspp"
                  (payload: an (H, W, C) image).
    deadline_s -- optional deadline RELATIVE to submission; the absolute
                  deadline is stamped by `submit`.  An expired request is
                  dropped (counted as a miss), never served late silently.
    """
    uid: Optional[int]
    kind: str
    payload: np.ndarray
    deadline_s: Optional[float] = None
    deadline: Optional[float] = dataclasses.field(default=None, repr=False)
    submitted: Optional[float] = dataclasses.field(default=None, repr=False)


class CircuitBreaker:
    """Per-(bucket, rung) quarantine: CLOSED -> OPEN after
    `fail_threshold` consecutive failures; OPEN counts down `cooldown`
    launch opportunities, then HALF_OPEN admits one probe; the probe's
    outcome closes or re-opens.  `transitions` records every state
    change for the state-machine tests."""

    def __init__(self, fail_threshold: int = 2, cooldown: int = 3):
        if fail_threshold < 1 or cooldown < 1:
            raise ValueError("fail_threshold and cooldown must be >= 1")
        self.fail_threshold = fail_threshold
        self.cooldown = cooldown
        self.state = "closed"
        self.failures = 0
        self._cool = 0
        self.transitions: List[Tuple[str, str]] = []

    def _to(self, state: str) -> None:
        if state != self.state:
            self.transitions.append((self.state, state))
            self.state = state

    def allow(self) -> bool:
        """May the next launch try this rung?  An OPEN breaker consumes
        one cooldown tick per refusal, so quarantine is measured in
        launch opportunities -- deterministic under test, no clocks."""
        if self.state == "closed":
            return True
        if self.state == "open":
            self._cool -= 1
            if self._cool > 0:
                return False
            self._to("half_open")
            return True
        return True   # half_open: the single-threaded engine probes once

    def record_success(self) -> None:
        self.failures = 0
        self._to("closed")

    def record_failure(self) -> None:
        self.failures += 1
        if self.state == "half_open" or self.failures >= self.fail_threshold:
            self.failures = 0
            self._cool = self.cooldown
            self._to("open")


class _GcSpan:
    """`gc.callbacks` entry that opens a `python.gc` span when a
    collection starts and closes it when the collection stops, so a
    trace labels the host pauses that are collections."""

    def __init__(self, annotation):
        self._annotation = annotation
        self._open = None

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._open = self._annotation("python.gc",
                                          generation=info["generation"])
            self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


def _register_gc_span(annotation) -> None:
    """Add the `python.gc` span to `gc.callbacks`, once per process."""
    if not any(isinstance(cb, _GcSpan) for cb in gc.callbacks):
        gc.callbacks.append(_GcSpan(annotation))


@dataclasses.dataclass
class _Bucket:
    key: tuple
    kind: str
    payload_shape: tuple
    specs: tuple              # the ConvSpec-normalized launch geometry
    breakers: Dict[str, CircuitBreaker]
    device_slots: bool        # payloads cross one slot at a time (`_slots`)
    zero_slot: object = None  # the empty slot, made on the device once


class ConvServeEngine:
    """Continuous-batching request manager over the GAN generator and
    the ASPP atrous head.  Single-threaded and synchronous by design
    (the edge-serving regime this models has one accelerator): `submit`
    admits or sheds, `run` drains the queue, `serve` does both."""

    def __init__(self, *, gan_params=None, aspp_params=None,
                 slot_batch: int = 4, queue_limit: int = 32,
                 ladder: Sequence[str] = DEFAULT_LADDER,
                 injector: Optional[FaultInjector] = None,
                 fail_threshold: int = 2, cooldown: int = 3,
                 retry_backoff_s: float = 0.0,
                 max_backoff_s: float = 0.05,
                 rates: Tuple[int, ...] = (1, 2, 4),
                 fuse_epilogue: bool = True,
                 tile_cache_path=None):
        if slot_batch < 1 or queue_limit < 1:
            raise ValueError("slot_batch and queue_limit must be >= 1")
        if not ladder:
            raise ValueError("ladder must name at least one backend")
        self.gan_params = gan_params
        self.aspp_params = aspp_params
        self.slot_batch = int(slot_batch)
        self.queue_limit = int(queue_limit)
        self.ladder = tuple(ladder)
        self.injector = injector
        self.fail_threshold = int(fail_threshold)
        self.cooldown = int(cooldown)
        self.retry_backoff_s = float(retry_backoff_s)
        self.max_backoff_s = float(max_backoff_s)
        self.rates = tuple(rates)
        self.fuse_epilogue = bool(fuse_epilogue)
        self.tile_cache_path = tile_cache_path

        self._queue: deque = deque()
        self._buckets: Dict[tuple, _Bucket] = {}
        self._jit_cache: Dict[tuple, object] = {}
        self._next_uid = 0
        self._latencies_us: deque = deque(maxlen=LATENCY_WINDOW)
        from jax.profiler import TraceAnnotation
        self._span = TraceAnnotation
        _register_gc_span(TraceAnnotation)
        self.stats: Dict[str, object] = {
            "submitted": 0, "completed": 0, "sheds": 0, "failures": 0,
            "retries": 0, "fallbacks": 0, "nan_events": 0,
            "deadline_misses": 0, "kernel_faults": 0, "quarantines": 0,
            "reprobes": 0, "launches": 0, "h2d_bytes": 0, "warmup": None,
        }

    # -- buckets ----------------------------------------------------------

    def _bucket(self, kind: str, payload_shape: tuple) -> _Bucket:
        key = (kind, tuple(int(s) for s in payload_shape))
        b = self._buckets.get(key)
        if b is not None:
            return b
        entries = self._plan_entries(kind, key[1])
        b = _Bucket(
            key=key, kind=kind, payload_shape=key[1],
            specs=tuple(e[1] for e in entries),
            breakers={name: CircuitBreaker(self.fail_threshold,
                                           self.cooldown)
                      for name in self.ladder},
            device_slots=(np.dtype(np.float32).itemsize * math.prod(key[1])
                          >= DEVICE_SLOT_MIN_BYTES))
        self._buckets[key] = b
        return b

    def _plan_entries(self, kind: str, payload_shape: tuple):
        """The bucket's launch geometry, normalized through
        `ConvSpec.make` by the model helpers."""
        if kind == "gan_gen":
            if self.gan_params is None:
                raise ValueError("no gan_params: cannot serve gan_gen")
            from repro.models import gan
            return gan.generator_plan_requests(
                self.gan_params, self.slot_batch,
                fuse_epilogue=self.fuse_epilogue)
        if kind == "aspp":
            if self.aspp_params is None:
                raise ValueError("no aspp_params: cannot serve aspp")
            from repro.models import vision
            return vision.atrous_plan_requests(
                self.aspp_params, (self.slot_batch,) + payload_shape,
                rates=self.rates, fuse_epilogue=self.fuse_epilogue)
        raise ValueError(f"unknown request kind {kind!r}; "
                         f"expected one of {KINDS}")

    def forward_fn(self, kind: str, backend: str):
        """The bucket's raw (unjitted) launch callable for `backend` --
        the jaxpr-pin surface: tracing it with injection off shows
        exactly the training stack's launch structure."""
        if kind == "gan_gen":
            from repro.models import gan
            return lambda batch: gan.generator_apply(
                self.gan_params, batch, backend=backend,
                fuse_epilogue=self.fuse_epilogue)
        if kind == "aspp":
            from repro.models import vision
            return lambda batch: vision.atrous_head_apply(
                self.aspp_params, batch, rates=self.rates,
                backend=backend, fuse_epilogue=self.fuse_epilogue)
        raise ValueError(f"unknown request kind {kind!r}")

    def _jitted(self, bucket: _Bucket, backend: str):
        """The bucket's compiled launch for `backend`, called with
        `_slots`' arguments: on the device-slot path it stacks the
        `slot_batch` slots inside the jit, so one program serves every
        cohort size."""
        key = (bucket.key, backend)
        fn = self._jit_cache.get(key)
        if fn is None:
            import jax
            fwd = self.forward_fn(bucket.kind, backend)
            if bucket.device_slots:
                import jax.numpy as jnp
                fn = jax.jit(lambda *slots: fwd(jnp.stack(slots)))
            else:
                fn = jax.jit(fwd)
            self._jit_cache[key] = fn
        return fn

    def _slots(self, bucket: _Bucket, payloads) -> tuple:
        """The launch's arguments for `payloads`, the slots past them
        empty.  Host path: one zero-padded float32 slot batch.
        Device-slot path: each payload put on the device as float32 (its
        bytes counted in `h2d_bytes`), every empty slot the bucket's zero
        slot, made on the device and never transferred.  Nothing put here
        outlives the launch: a payload crosses once per request."""
        if not bucket.device_slots:
            batch = np.zeros((self.slot_batch,) + bucket.payload_shape,
                             np.float32)
            for i, p in enumerate(payloads):
                batch[i] = p
            return (batch,)
        import jax
        if bucket.zero_slot is None:
            import jax.numpy as jnp
            bucket.zero_slot = jnp.zeros(bucket.payload_shape, jnp.float32)
        put = jax.device_put([np.asarray(p, np.float32) for p in payloads])
        self.stats["h2d_bytes"] += sum(a.nbytes for a in put)
        return (*put,) + (bucket.zero_slot,) * (self.slot_batch - len(put))

    # -- warmup -----------------------------------------------------------

    def warmup(self, shapes: Sequence[Tuple[str, tuple]], *,
               compile: bool = False) -> dict:
        """Pre-plan every bucket's tiles from the shipped tile-cache
        artifact (never an autotune sweep; a corrupt artifact warns and
        falls back to the analytical planner) and optionally pre-compile
        the primary rung's launch, called as `_launch` calls it, on empty
        slots.  `shapes` lists ``(kind, payload_shape)`` pairs."""
        from repro.kernels import tiling
        from repro.kernels.ops import interpret_mode
        interpret = interpret_mode()
        entries = []
        for kind, payload_shape in shapes:
            bucket = self._bucket(kind, tuple(payload_shape))
            entries.extend(self._plan_entries(kind, bucket.payload_shape))
        plans = tiling.warmup_plans(entries,
                                    tile_cache_path=self.tile_cache_path,
                                    interpret=interpret)
        summary = {
            "buckets": len(self._buckets),
            "plans": len(plans),
            "artifact": sum(1 for v in plans.values()
                            if v["source"] == "artifact"),
            "analytical": sum(1 for v in plans.values()
                              if v["source"] == "analytical"),
        }
        if compile:
            for kind, payload_shape in shapes:
                bucket = self._bucket(kind, tuple(payload_shape))
                np.asarray(self._jitted(bucket, self.ladder[0])(
                    *self._slots(bucket, [])))
        self.stats["warmup"] = summary
        return summary

    # -- admission --------------------------------------------------------

    def submit(self, req: ConvRequest) -> bool:
        """Admit `req` into the bounded queue; False (and a shed count)
        when the queue is at the admission bound."""
        self.stats["submitted"] += 1
        if len(self._queue) >= self.queue_limit:
            self.stats["sheds"] += 1
            return False
        if req.uid is None:
            req.uid = self._next_uid
            self._next_uid += 1
        req.submitted = time.monotonic()
        if req.deadline_s is not None:
            req.deadline = req.submitted + req.deadline_s
        self._bucket(req.kind, tuple(req.payload.shape))
        self._queue.append(req)
        return True

    # -- serving loop -----------------------------------------------------

    def serve(self, requests: Sequence[ConvRequest]) -> Dict[int, np.ndarray]:
        """Submit a batch of requests (shedding past the admission
        bound) and drain the queue.  Returns {uid: result} for every
        admitted request that completed in deadline."""
        for r in requests:
            self.submit(r)
        return self.run()

    def run(self) -> Dict[int, np.ndarray]:
        """Drain the queue: take up to `slot_batch` same-bucket requests
        from the front (slots refill from the queue on every launch),
        launch them through the degradation ladder, repeat."""
        results: Dict[int, np.ndarray] = {}
        span = self._span
        while self._queue:
            with span("engine.cohort"):
                with span("engine.take"):
                    cohort, bucket = self._take_cohort()
                if not cohort:
                    continue
                out = self._launch(bucket, cohort)
                if out is None:       # every rung failed for this cohort
                    self.stats["failures"] += len(cohort)
                    continue
                with span("engine.answer"):
                    now = time.monotonic()
                    for i, r in enumerate(cohort):
                        if r.deadline is not None and now > r.deadline:
                            self.stats["deadline_misses"] += 1
                            continue
                        self.stats["completed"] += 1
                        self._latencies_us.append((now - r.submitted) * 1e6)
                        results[r.uid] = out[i]
        return results

    def _take_cohort(self):
        """Pop up to `slot_batch` requests sharing the front request's
        bucket, preserving the order of everything left behind.
        Already-expired requests are dropped here (deadline miss)."""
        now = time.monotonic()
        while self._queue:
            head = self._queue[0]
            if head.deadline is not None and now > head.deadline:
                self._queue.popleft()
                self.stats["deadline_misses"] += 1
                continue
            break
        if not self._queue:
            return [], None
        head = self._queue[0]
        bucket = self._bucket(head.kind, tuple(head.payload.shape))
        cohort, rest = [], deque()
        while self._queue and len(cohort) < self.slot_batch:
            r = self._queue.popleft()
            if r.deadline is not None and now > r.deadline:
                self.stats["deadline_misses"] += 1
                continue
            if (r.kind, tuple(r.payload.shape)) == bucket.key:
                cohort.append(r)
            else:
                rest.append(r)
        rest.extend(self._queue)
        self._queue = rest
        return cohort, bucket

    def _rungs(self, bucket: _Bucket) -> List[str]:
        """The ladder filtered through the breakers.  When every rung is
        quarantined the LAST rung is forced anyway: a fully-open ladder
        must still answer (never hang, never drop silently)."""
        allowed = [name for name in self.ladder
                   if bucket.breakers[name].allow()]
        return allowed if allowed else [self.ladder[-1]]

    def _launch(self, bucket: _Bucket, cohort) -> Optional[np.ndarray]:
        """One slot-batch launch through the ladder.  Returns the host
        output batch, or None when every rung (and the NaN retry budget)
        is exhausted.  Every attempt reuses the slots built once: on the
        device-slot path a retry sends nothing again, while a host slot
        batch crosses (and is counted) with each jitted call."""
        span = self._span
        n = len(cohort)
        with span("engine.launch", kind=bucket.kind, n=n,
                  uid0=cohort[0].uid):
            with span("engine.batch"):
                slots = self._slots(bucket, [r.payload for r in cohort])
            host_bytes = 0 if bucket.device_slots else slots[0].nbytes
            self.stats["launches"] += 1
            attempt = 0
            rungs = self._rungs(bucket)
            for ri, backend in enumerate(rungs):
                breaker = bucket.breakers[backend]
                probing = breaker.state == "half_open"
                if probing:
                    self.stats["reprobes"] += 1
                nan_budget = 1
                while True:
                    if attempt > 0:
                        self.stats["retries"] += 1
                        self._backoff(attempt)
                    attempt += 1
                    try:
                        with span("engine.dispatch", rung=backend,
                                  attempt=attempt):
                            ev = None
                            if self.injector is not None:
                                ev = self.injector.raise_or_delay(
                                    f"{bucket.kind}:{backend}")
                            y = self._jitted(bucket, backend)(*slots)
                            self.stats["h2d_bytes"] += host_bytes
                        with span("engine.fetch"):
                            out = np.asarray(y)
                        if ev is not None:
                            out = self.injector.poison(ev, out)
                    except Exception:  # noqa: BLE001 - ladder absorbs faults
                        self.stats["kernel_faults"] += 1
                        self._fail(breaker)
                        break     # degrade: next rung serves this cohort
                    with span("engine.check"):
                        finite = bool(np.all(np.isfinite(out[:n])))
                    if not finite:
                        self.stats["nan_events"] += 1
                        if nan_budget > 0:
                            nan_budget -= 1
                            continue  # transient? one retry on the same rung
                        self._fail(breaker)
                        break     # systematic: degrade to the next rung
                    breaker.record_success()
                    if ri > 0:
                        self.stats["fallbacks"] += 1
                    return out
        return None

    def _fail(self, breaker: CircuitBreaker) -> None:
        before = breaker.state
        breaker.record_failure()
        if breaker.state == "open" and before != "open":
            self.stats["quarantines"] += 1

    def _backoff(self, attempt: int) -> None:
        if self.retry_backoff_s <= 0:
            return
        time.sleep(min(self.max_backoff_s,
                       self.retry_backoff_s * (2.0 ** (attempt - 1))))

    # -- health -----------------------------------------------------------

    def health(self) -> dict:
        """Stats snapshot plus latency percentiles and breaker states --
        the surface a deployment scrapes.  `p50_us` / `p99_us` are taken
        over the most recent `LATENCY_WINDOW` answers, each timed from
        its `submit` to its answer on the host."""
        lat = np.asarray(self._latencies_us, np.float64)
        out = dict(self.stats)
        out["p50_us"] = float(np.percentile(lat, 50)) if lat.size else None
        out["p99_us"] = float(np.percentile(lat, 99)) if lat.size else None
        out["queue_depth"] = len(self._queue)
        out["breakers"] = {
            f"{k[0]}:{name}": br.state
            for k, b in self._buckets.items()
            for name, br in b.breakers.items()}
        return out
