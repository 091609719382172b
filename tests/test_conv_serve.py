"""`ConvServeEngine`: geometry buckets, degradation ladder, breakers,
deadlines, shedding -- plus the LM `ServeEngine` mid-flight slot refill.

The acceptance pins (ISSUE 9): under a seeded fault schedule injecting
kernel exceptions, NaN outputs, and a corrupt tile cache, the engine
completes 100% of in-deadline requests with results bit-matching the
reference backend; the failing backend is quarantined and later
re-probed; requests beyond the admission bound are shed, never hung on;
and with injection off the fast path stays at ONE forward `pallas_call`
per conv layer.
"""
from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import assert_allclose, count_pallas_calls
from repro.models import gan, vision
from repro.serve import conv_engine
from repro.serve.conv_engine import (ConvRequest, ConvServeEngine,
                                     CircuitBreaker, DEFAULT_LADDER)
from repro.serve.faults import (FaultEvent, FaultInjector, FaultSchedule,
                                corrupt_tile_cache)

Z_DIM, BASE = 8, 8
IMG = (8, 8, 3)
IMG_BYTES = 4 * int(np.prod(IMG))          # a float32 ASPP payload
HOST_SLOTS, DEVICE_SLOTS = 1 << 62, 1      # DEVICE_SLOT_MIN_BYTES per path
SLOTS = 4


@pytest.fixture(scope="module")
def gan_params():
    return gan.generator_init(jax.random.PRNGKey(0), z_dim=Z_DIM,
                              base=BASE, out_ch=3)


@pytest.fixture(scope="module")
def aspp_params():
    return vision.atrous_head_init(jax.random.PRNGKey(1), in_ch=IMG[2],
                                   width=4, n_classes=4)


def _gan_reqs(rng, n, **kw):
    return [ConvRequest(None, "gan_gen",
                        rng.standard_normal(Z_DIM).astype(np.float32), **kw)
            for _ in range(n)]


def _aspp_reqs(rng, n, **kw):
    return [ConvRequest(None, "aspp",
                        rng.standard_normal(IMG).astype(np.float32), **kw)
            for _ in range(n)]


# ---------------------------------------------------------------------------
# Clean path
# ---------------------------------------------------------------------------

def test_serves_both_buckets_clean(gan_params, aspp_params, rng):
    eng = ConvServeEngine(gan_params=gan_params, aspp_params=aspp_params,
                          slot_batch=2, queue_limit=16)
    reqs = _gan_reqs(rng, 3) + _aspp_reqs(rng, 2) + _gan_reqs(rng, 1)
    res = eng.serve(reqs)
    assert len(res) == 6                       # interleaved buckets all land
    for r in reqs:
        out = res[r.uid]
        assert np.all(np.isfinite(out))
        assert out.shape == ((32, 32, 3) if r.kind == "gan_gen"
                             else (8, 8, 4))
    h = eng.health()
    assert h["completed"] == 6 and h["sheds"] == 0 and h["failures"] == 0
    assert h["p50_us"] is not None and h["p99_us"] >= h["p50_us"]


def test_clean_parity_vs_direct_apply(gan_params, rng):
    """Bucketed, padded serving returns exactly what a direct jitted
    batch apply returns for the same rows."""
    n = 3
    eng = ConvServeEngine(gan_params=gan_params, slot_batch=n,
                          queue_limit=8)
    reqs = _gan_reqs(rng, n)
    res = eng.serve(reqs)
    batch = np.stack([r.payload for r in reqs])
    direct = np.asarray(jax.jit(
        lambda z: gan.generator_apply(gan_params, z,
                                      backend=DEFAULT_LADDER[0]))(batch))
    for i, r in enumerate(reqs):
        assert np.array_equal(res[r.uid], direct[i])


# ---------------------------------------------------------------------------
# Acceptance: seeded faults -> 100% in-deadline completion, reference parity
# ---------------------------------------------------------------------------

def _always_fail(sites, seed=5):
    return FaultInjector(FaultSchedule.seeded(
        seed, sites=list(sites), rate=1.0, horizon=1024,
        kinds=("kernel_exception",)))


@pytest.mark.parametrize("kind", ["gan_gen", "aspp"])
def test_full_degradation_bit_matches_reference(gan_params, aspp_params,
                                                rng, kind):
    """Kernel exceptions on every non-reference rung force each bucket
    down to `reference`; served results must be BIT-identical to the
    reference backend's own jitted batch output."""
    n = 2
    inj = _always_fail([f"{kind}:pallas", f"{kind}:xla_zero_free"])
    eng = ConvServeEngine(gan_params=gan_params, aspp_params=aspp_params,
                          slot_batch=n, queue_limit=8, injector=inj)
    reqs = _gan_reqs(rng, n) if kind == "gan_gen" else _aspp_reqs(rng, n)
    res = eng.serve(reqs)
    assert len(res) == n                       # 100% completion
    batch = np.stack([r.payload for r in reqs])
    if kind == "gan_gen":
        fn = lambda b: gan.generator_apply(gan_params, b,
                                           backend="reference")
    else:
        fn = lambda b: vision.atrous_head_apply(aspp_params, b,
                                                backend="reference")
    expect = np.asarray(jax.jit(fn)(batch))
    for i, r in enumerate(reqs):
        assert np.array_equal(res[r.uid], expect[i]), r.uid
    h = eng.health()
    assert h["kernel_faults"] >= 2 and h["fallbacks"] >= 1


def test_mixed_fault_storm_completes_all(gan_params, rng, tmp_path):
    """The ISSUE's composite scenario: kernel exceptions AND NaN outputs
    on the fast rungs AND a corrupt tile-cache artifact.  Warmup warns
    (and re-plans); every admitted request still completes with a finite
    result."""
    cache = tmp_path / "tile_cache.json"
    corrupt_tile_cache(cache, "garbage")
    inj = FaultInjector(FaultSchedule.seeded(
        13, sites=["gan_gen:pallas", "gan_gen:xla_zero_free"], rate=0.4,
        horizon=1024, kinds=("kernel_exception", "nan_output")))
    eng = ConvServeEngine(gan_params=gan_params, slot_batch=2,
                          queue_limit=32, injector=inj,
                          tile_cache_path=cache)
    with pytest.warns(RuntimeWarning):
        summary = eng.warmup([("gan_gen", (Z_DIM,))])
    assert summary["analytical"] == summary["plans"] > 0
    reqs = _gan_reqs(rng, 10)
    res = eng.serve(reqs)
    assert len(res) == 10                      # 100% of in-deadline requests
    for r in reqs:
        assert np.all(np.isfinite(res[r.uid]))
    assert len(inj.fired) > 0                  # the storm actually fired


def test_nan_guard_retries_once_then_degrades(gan_params, rng):
    """nan_output twice in a row on the first rung: one same-rung retry,
    then degrade -- the result comes from the next rung, finite."""
    inj = FaultInjector(FaultSchedule([
        FaultEvent("gan_gen:pallas", 0, "nan_output"),
        FaultEvent("gan_gen:pallas", 1, "nan_output")]))
    eng = ConvServeEngine(gan_params=gan_params, slot_batch=1,
                          queue_limit=4, injector=inj)
    res = eng.serve(_gan_reqs(rng, 1))
    assert len(res) == 1 and np.all(np.isfinite(next(iter(res.values()))))
    h = eng.health()
    assert h["nan_events"] == 2                # original + one retry
    assert h["retries"] >= 1 and h["fallbacks"] == 1


def test_transient_nan_recovers_on_same_rung(gan_params, rng):
    inj = FaultInjector(FaultSchedule([
        FaultEvent("gan_gen:pallas", 0, "nan_output")]))
    eng = ConvServeEngine(gan_params=gan_params, slot_batch=1,
                          queue_limit=4, injector=inj)
    res = eng.serve(_gan_reqs(rng, 1))
    assert len(res) == 1
    h = eng.health()
    assert h["nan_events"] == 1 and h["fallbacks"] == 0
    assert h["breakers"]["gan_gen:pallas"] == "closed"


# ---------------------------------------------------------------------------
# Acceptance: circuit breaker quarantine -> re-probe state transitions
# ---------------------------------------------------------------------------

def test_quarantine_then_reprobe_state_machine(gan_params, rng):
    """pallas raises on its first two launches (threshold 2 -> OPEN);
    quarantined launches skip it; after the cooldown the breaker
    half-opens, the probe succeeds, and the rung closes again."""
    inj = FaultInjector(FaultSchedule([
        FaultEvent("gan_gen:pallas", 0, "kernel_exception"),
        FaultEvent("gan_gen:pallas", 1, "kernel_exception")]))
    eng = ConvServeEngine(gan_params=gan_params, slot_batch=1,
                          queue_limit=8, injector=inj,
                          fail_threshold=2, cooldown=2)
    res = eng.serve(_gan_reqs(rng, 4))
    assert len(res) == 4
    br = eng._buckets[("gan_gen", (Z_DIM,))].breakers["pallas"]
    assert br.transitions == [("closed", "open"), ("open", "half_open"),
                              ("half_open", "closed")]
    h = eng.health()
    assert h["quarantines"] == 1 and h["reprobes"] == 1
    assert h["breakers"]["gan_gen:pallas"] == "closed"
    # launches 1-2 degraded, 3 was quarantined, 4 was the probe: the
    # injector only ever saw pallas three times
    assert inj._counters["gan_gen:pallas"] == 3


def test_reprobe_failure_reopens(gan_params, rng):
    inj = FaultInjector(FaultSchedule([
        FaultEvent("gan_gen:pallas", 0, "kernel_exception"),
        FaultEvent("gan_gen:pallas", 1, "kernel_exception"),
        FaultEvent("gan_gen:pallas", 2, "kernel_exception")]))
    eng = ConvServeEngine(gan_params=gan_params, slot_batch=1,
                          queue_limit=8, injector=inj,
                          fail_threshold=2, cooldown=2)
    res = eng.serve(_gan_reqs(rng, 4))
    assert len(res) == 4
    br = eng._buckets[("gan_gen", (Z_DIM,))].breakers["pallas"]
    assert br.transitions == [("closed", "open"), ("open", "half_open"),
                              ("half_open", "open")]


def test_breaker_unit_semantics():
    br = CircuitBreaker(fail_threshold=2, cooldown=3)
    assert br.allow() and br.state == "closed"
    br.record_failure()
    assert br.state == "closed"                # below threshold
    br.record_failure()
    assert br.state == "open"
    assert not br.allow() and not br.allow()   # cooldown ticks 2, 1
    assert br.allow() and br.state == "half_open"
    br.record_success()
    assert br.state == "closed"
    with pytest.raises(ValueError):
        CircuitBreaker(fail_threshold=0)


def test_fully_open_ladder_still_answers(gan_params, rng):
    """Even with EVERY rung quarantined the engine forces the last rung:
    it may be slow, it may fail, but it never refuses to try."""
    inj = _always_fail(["gan_gen:pallas", "gan_gen:xla_zero_free",
                        "gan_gen:reference"])
    eng = ConvServeEngine(gan_params=gan_params, slot_batch=1,
                          queue_limit=8, injector=inj,
                          fail_threshold=1, cooldown=100)
    res = eng.serve(_gan_reqs(rng, 3))
    assert res == {}                           # everything fails...
    h = eng.health()
    assert h["failures"] == 3                  # ...but is ACCOUNTED, no hang
    assert h["launches"] == 3


# ---------------------------------------------------------------------------
# Acceptance: bounded admission -> shed, never hang
# ---------------------------------------------------------------------------

def test_admission_bound_sheds(gan_params, rng):
    eng = ConvServeEngine(gan_params=gan_params, slot_batch=2,
                          queue_limit=3)
    reqs = _gan_reqs(rng, 8)
    admitted = [eng.submit(r) for r in reqs]
    assert admitted == [True] * 3 + [False] * 5
    res = eng.run()
    assert len(res) == 3
    h = eng.health()
    assert h["sheds"] == 5 and h["completed"] == 3
    assert h["queue_depth"] == 0


def test_deadline_expired_request_is_dropped(gan_params, rng):
    eng = ConvServeEngine(gan_params=gan_params, slot_batch=2,
                          queue_limit=8)
    live = _gan_reqs(rng, 2, deadline_s=60.0)
    dead = _gan_reqs(rng, 1, deadline_s=0.0)
    res = eng.serve(live + dead)
    assert set(res) == {r.uid for r in live}
    assert eng.health()["deadline_misses"] == 1


def test_latency_spike_misses_deadline(gan_params, rng):
    """A straggler (injected latency spike) pushes completion past the
    request's deadline: the result is withheld and counted as a miss."""
    eng = ConvServeEngine(gan_params=gan_params, slot_batch=1,
                          queue_limit=4)
    eng.serve(_gan_reqs(rng, 1))               # compile outside the window
    eng.injector = FaultInjector(FaultSchedule([
        FaultEvent("gan_gen:pallas", 0, "latency_spike", magnitude=0.3)]))
    res = eng.serve(_gan_reqs(rng, 1, deadline_s=0.05))
    assert res == {}
    assert eng.health()["deadline_misses"] == 1
    assert eng.health()["completed"] == 1      # only the warm request


# ---------------------------------------------------------------------------
# Acceptance: injection off -> ONE forward pallas_call per conv layer
# ---------------------------------------------------------------------------

def test_fast_path_single_launch_per_layer(gan_params, aspp_params):
    eng = ConvServeEngine(gan_params=gan_params, aspp_params=aspp_params,
                          slot_batch=2, queue_limit=4)
    z = jnp.zeros((2, Z_DIM), jnp.float32)
    # three transposed-conv layers -> exactly three pallas_calls
    assert count_pallas_calls(eng.forward_fn("gan_gen", "pallas"), z) == 3
    img = jnp.zeros((2,) + IMG, jnp.float32)
    # three dilated branches -> three pallas_calls (the 1x1 fuse conv is
    # an XLA matmul-shaped conv by design, same as training)
    assert count_pallas_calls(eng.forward_fn("aspp", "pallas"), img) == 3
    # and the reference rung launches no pallas at all
    assert count_pallas_calls(eng.forward_fn("gan_gen", "reference"),
                              z) == 0


def test_bucket_normalizes_through_convspec(gan_params, aspp_params):
    from repro.core.spec import ConvSpec
    eng = ConvServeEngine(gan_params=gan_params, aspp_params=aspp_params,
                          slot_batch=2, queue_limit=4)
    b = eng._bucket("gan_gen", (Z_DIM,))
    assert all(isinstance(s, ConvSpec) for s in b.specs)
    assert [s.stride for s in b.specs] == [(2, 2)] * 3
    b2 = eng._bucket("aspp", IMG)
    assert [s.dilation for s in b2.specs] == [(1, 1), (2, 2), (4, 4),
                                              (1, 1)]
    # same geometry -> same bucket object (compile-once)
    assert eng._bucket("gan_gen", (Z_DIM,)) is b
    with pytest.raises(ValueError):
        eng._bucket("bogus", (1,))


def test_warmup_pre_compiles_primary(gan_params, rng, monkeypatch):
    """On both slot paths, warmup compiles exactly the launch that serving
    calls: a real launch adds no jit-cache entry and compiles nothing."""
    for min_bytes in (HOST_SLOTS, DEVICE_SLOTS):
        monkeypatch.setattr(conv_engine, "DEVICE_SLOT_MIN_BYTES", min_bytes)
        eng = ConvServeEngine(gan_params=gan_params, slot_batch=2,
                              queue_limit=4)
        eng.warmup([("gan_gen", (Z_DIM,))], compile=True)
        key = (("gan_gen", (Z_DIM,)), "pallas")
        assert set(eng._jit_cache) == {key}
        assert eng.health()["warmup"]["buckets"] == 1
        assert eng._buckets[key[0]].device_slots == (min_bytes == 1)
        compiled = eng._jit_cache[key]._cache_size()
        assert len(eng.serve(_gan_reqs(rng, 1))) == 1
        assert set(eng._jit_cache) == {key}
        assert eng._jit_cache[key]._cache_size() == compiled


# ---------------------------------------------------------------------------
# Device-slot path: large payloads cross one slot at a time, the empty
# slots are made on the device
# ---------------------------------------------------------------------------

def _serve_cohorts(monkeypatch, aspp_params, min_bytes, cohorts, **kw):
    """Serve each cohort of payloads as one launch on a fresh engine whose
    buckets route by `min_bytes`; returns the engine, {uid: answer} and
    `h2d_bytes` after each launch."""
    monkeypatch.setattr(conv_engine, "DEVICE_SLOT_MIN_BYTES", min_bytes)
    eng = ConvServeEngine(aspp_params=aspp_params, slot_batch=SLOTS,
                          queue_limit=16, **kw)
    answers, h2d, uid = {}, [], 0
    for payloads in cohorts:
        for p in payloads:
            eng.submit(ConvRequest(uid, "aspp", p))
            uid += 1
        answers.update(eng.run())
        h2d.append(eng.stats["h2d_bytes"])
    assert eng.stats["launches"] == len(cohorts)
    return eng, answers, h2d


def test_payload_size_picks_the_slot_path(aspp_params, monkeypatch):
    assert 4 * 128 < conv_engine.DEVICE_SLOT_MIN_BYTES <= 4 * 33 * 33 * 2048
    for min_bytes, device in ((IMG_BYTES, True), (IMG_BYTES + 1, False)):
        monkeypatch.setattr(conv_engine, "DEVICE_SLOT_MIN_BYTES", min_bytes)
        eng = ConvServeEngine(aspp_params=aspp_params, slot_batch=SLOTS)
        assert eng._bucket("aspp", IMG).device_slots is device


@pytest.mark.parametrize("n", [1, 2, SLOTS])
def test_device_slots_answer_as_the_host_batch(aspp_params, rng,
                                               monkeypatch, n):
    """Bit-equal answers for a cohort of `n`; the device path puts only
    the cohort's own payloads, the host path the whole slot batch."""
    payloads = [rng.standard_normal(IMG).astype(np.float32)
                for _ in range(n)]
    _, host, host_h2d = _serve_cohorts(monkeypatch, aspp_params,
                                       HOST_SLOTS, [payloads])
    eng, dev, dev_h2d = _serve_cohorts(monkeypatch, aspp_params,
                                       DEVICE_SLOTS, [payloads])
    assert eng._buckets[("aspp", IMG)].device_slots
    assert sorted(dev) == sorted(host) == list(range(n))
    for uid in host:
        assert np.array_equal(dev[uid], host[uid]), uid
    assert host_h2d == [SLOTS * IMG_BYTES]
    assert dev_h2d == [n * IMG_BYTES]


@pytest.mark.parametrize("min_bytes,per_slot", [
    (HOST_SLOTS, lambda n: SLOTS), (DEVICE_SLOTS, lambda n: n)],
    ids=["host", "device"])
def test_h2d_bytes_per_launch(aspp_params, rng, monkeypatch, min_bytes,
                              per_slot):
    sizes = [1, 3, SLOTS, 2]
    cohorts = [[rng.standard_normal(IMG).astype(np.float32)
                for _ in range(n)] for n in sizes]
    _, answers, h2d = _serve_cohorts(monkeypatch, aspp_params, min_bytes,
                                     cohorts)
    assert len(answers) == sum(sizes)
    assert np.diff([0] + h2d).tolist() == [per_slot(n) * IMG_BYTES
                                          for n in sizes]


@pytest.mark.parametrize("events,fallbacks", [
    ([FaultEvent("aspp:pallas", 0, "nan_output")], 0),
    ([FaultEvent("aspp:pallas", 0, "kernel_exception")], 1),
    ([FaultEvent("aspp:pallas", 0, "nan_output"),
      FaultEvent("aspp:pallas", 1, "nan_output")], 1)],
    ids=["nan-retry", "exception-fallback", "nan-fallback"])
def test_device_slot_retry_sends_nothing_again(aspp_params, rng,
                                               monkeypatch, events,
                                               fallbacks):
    """A same-rung NaN retry and a fall to the next rung reuse the slots
    already on the device: answers as the host path's under the same
    faults, and `h2d_bytes` holds the cohort's payloads once."""
    n = 2
    payloads = [rng.standard_normal(IMG).astype(np.float32)
                for _ in range(n)]
    runs = {}
    for min_bytes in (HOST_SLOTS, DEVICE_SLOTS):
        inj = FaultInjector(FaultSchedule(list(events)))
        runs[min_bytes] = _serve_cohorts(monkeypatch, aspp_params, min_bytes,
                                         [payloads], injector=inj)
    eng, dev, dev_h2d = runs[DEVICE_SLOTS]
    _, host, _ = runs[HOST_SLOTS]
    assert eng.stats["fallbacks"] == fallbacks
    assert eng.stats["nan_events"] + eng.stats["kernel_faults"] == len(
        events)
    assert sorted(dev) == sorted(host) == list(range(n))
    for uid in host:
        assert np.all(np.isfinite(dev[uid]))
        assert np.array_equal(dev[uid], host[uid]), uid
    assert dev_h2d == [n * IMG_BYTES]


def test_no_device_copy_outlives_its_request(aspp_params, rng,
                                             monkeypatch):
    """Two cohorts carrying the same payload object each put it: its
    bytes count twice, and the engine keeps no device copy of it."""
    put = jax.device_put
    sent = []

    def recording_put(x, *a, **kw):
        sent.extend(np.shape(v) for v in jax.tree_util.tree_leaves(x))
        return put(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", recording_put)
    p = rng.standard_normal(IMG).astype(np.float32)
    eng, answers, h2d = _serve_cohorts(monkeypatch, aspp_params,
                                       DEVICE_SLOTS, [[p], [p]])
    assert h2d == [IMG_BYTES, 2 * IMG_BYTES]
    assert sent.count(IMG) == 2
    assert np.array_equal(answers[0], answers[1])
    bucket = eng._buckets[("aspp", IMG)]
    assert not np.any(np.asarray(bucket.zero_slot))


# ---------------------------------------------------------------------------
# Satellite: LM ServeEngine continuous batching (mid-flight slot refill)
# ---------------------------------------------------------------------------

def _lm_engine(batch=2, max_len=32):
    from repro.models.config import ModelConfig
    from repro.models.lm import LM
    from repro.serve.engine import ServeEngine
    cfg = ModelConfig(name="t", family="dense", n_layers=1, d_model=16,
                      d_ff=32, vocab=13, n_heads=2, n_kv_heads=2,
                      head_dim=8, dtype="float32", remat="none")
    params = LM(cfg).init(jax.random.PRNGKey(0))
    return ServeEngine(cfg, params, batch=batch, max_len=max_len)


def test_lm_slot_refill_mid_flight(rng):
    """3 requests, batch 2, one short request: the short sequence's slot
    must be reused by the queued request BEFORE the long one finishes."""
    from repro.serve.engine import Request
    eng = _lm_engine(batch=2)
    reqs = [Request(0, np.array([1, 2, 3], np.int32), max_new_tokens=8),
            Request(1, np.array([4, 5], np.int32), max_new_tokens=2),
            Request(2, np.array([6, 7, 8], np.int32), max_new_tokens=8)]
    res = eng.generate(reqs)
    assert set(res) == {0, 1, 2}
    assert len(res[0]) == 8 and len(res[1]) == 2 and len(res[2]) == 8
    # the regression pin: request 2 entered a slot freed MID-FLIGHT
    assert eng.stats["refills"] >= 1
    assert eng.stats["prefills"] >= 2


def test_lm_generate_single_cohort_unchanged(rng):
    from repro.serve.engine import Request
    eng = _lm_engine(batch=2)
    reqs = [Request(0, np.array([1, 2], np.int32), max_new_tokens=4),
            Request(1, np.array([3, 4], np.int32), max_new_tokens=4)]
    res = eng.generate(reqs)
    assert len(res[0]) == 4 and len(res[1]) == 4
    assert eng.stats["refills"] == 0           # no queue pressure
    assert all(0 <= t < 13 for t in res[0] + res[1])


def test_lm_eos_frees_slot(rng):
    """EOS retirement: whatever token the tiny model greedily emits
    first is declared EOS for request 0, so its slot frees after one
    token and the queued request refills it."""
    from repro.serve.engine import Request
    eng = _lm_engine(batch=1)
    probe = eng.generate([Request(9, np.array([1, 2], np.int32),
                                  max_new_tokens=1)])
    eos = probe[9][0]
    eng2 = _lm_engine(batch=1)
    reqs = [Request(0, np.array([1, 2], np.int32), max_new_tokens=8,
                    eos_id=int(eos)),
            Request(1, np.array([5, 6], np.int32), max_new_tokens=2)]
    res = eng2.generate(reqs)
    assert res[0] == [int(eos)]                # stopped at EOS
    assert len(res[1]) == 2
