"""Geometry-aware tile planner (`kernels/tiling.py`): analytical model
invariants (budget respected, exact channel tiles preferred, spatial
tiling under VMEM pressure, interpret-vs-compiled step weighting) and the
empirical autotune mode (candidate sweep through a registered runner,
JSON cache persistence, memory + disk cache hits)."""
from __future__ import annotations

import json

import pytest

from repro.core.spec import ConvSpec, Epilogue
from repro.kernels import tiling


def _shapes(B, N, O, Ci, Co):
    return (B, N, N, Ci), (B, O, O, Co)


def test_plan_respects_vmem_budget():
    """Every returned plan's modeled working set fits the budget, across
    op families, budgets and both execution modes -- or, where no
    candidate fits, the plan is the minimum-footprint candidate.
    Compiled plans are scored at the lane-padded VMEM layout and may
    only split channels into 128-lane tiles, so at this 256-channel
    geometry the full-frame kernels have no fitting compiled candidate
    below the default budget."""
    spec = ConvSpec.make(stride=2, padding=0, filter_shape=3)
    x_shape, dy_shape = _shapes(2, 127, 63, 256, 256)
    # filter_grad can always shrink its spatial slab to fit a tight
    # budget; forward/input_grad/backward hold a full spatial frame, so
    # only test budgets a frame can fit; ct_backward's working set has
    # an irreducible floor (full-Cout ddy row + full-channel stationary
    # dW block), so only the default budget is guaranteed feasible at
    # this 256-channel geometry.  Below the listed budgets the planner
    # falls back to the minimum-footprint candidate by design.
    budgets_by_op = {
        "filter_grad": (1 << 20, 4 << 20, tiling.DEFAULT_VMEM_BUDGET),
        "ct_backward": (tiling.DEFAULT_VMEM_BUDGET,),
    }
    for interpret in (True, False):
        for op in tiling.OPS:
            budgets = budgets_by_op.get(op, (4 << 20,
                                             tiling.DEFAULT_VMEM_BUDGET))
            for budget in budgets:
                plan = tiling.plan_tiles(op, spec, x_shape=x_shape,
                                         dy_shape=dy_shape,
                                         vmem_budget=budget,
                                         interpret=interpret)
                g = tiling._geom(op, spec, x_shape, dy_shape, 4)
                ws = tiling._working_set(
                    op, g, plan.cin_tile, plan.cout_tile,
                    plan.spatial_tile, plan.tap_unroll, plan.phase_unroll,
                    interpret)
                cands = list(tiling._candidates(op, g, "phase", interpret))
                if any(tiling._score(op, g, *c, budget, interpret)
                       is not None for c in cands):
                    assert ws <= budget, (op, budget, interpret, plan)
                else:
                    assert not interpret, (op, budget, plan)
                    assert ws == min(
                        tiling._working_set(op, g, *c, interpret)
                        for c in cands
                        if c[3] * c[4] <= tiling.MAX_TAP_UNROLL_COMPILED), (
                        op, budget, plan)
                assert plan.grid_order == tiling._GRID_ORDERS[op]
                assert plan.source == "analytical"


def test_exact_channel_tiles_preferred_when_small():
    """Sub-128 channel counts get their EXACT extent as the tile (no
    host pad/slice at all) -- the ShuffleNet-29 case that a hard-coded
    128 default handled with pad-to-128 waste."""
    spec = ConvSpec.make(stride=2, padding=0, filter_shape=3)
    x_shape, dy_shape = _shapes(1, 29, 14, 29, 29)
    for interpret in (False, True):
        plan = tiling.plan_tiles("filter_grad", spec, x_shape=x_shape,
                                 dy_shape=dy_shape, interpret=interpret)
        assert plan.cin_tile == 29 and plan.cout_tile == 29, plan


def test_spatial_tiling_engages_under_vmem_pressure():
    """A big padded frame with a tight budget forces the filter-grad x
    block down to a spatial slab (spatial_tile < Oh), instead of either
    busting the budget or shrinking channel tiles to nothing."""
    spec = ConvSpec.make(stride=1, padding=1, filter_shape=3)
    x_shape, dy_shape = _shapes(1, 256, 256, 64, 64)
    plan = tiling.plan_tiles("filter_grad", spec, x_shape=x_shape,
                             dy_shape=dy_shape, vmem_budget=1 << 20,
                             interpret=False)
    assert plan.spatial_tile < 256, plan
    g = tiling._geom("filter_grad", spec, x_shape, dy_shape, 4)
    ws, _, _, _ = tiling._MODELS["filter_grad"](
        g, plan.cin_tile, plan.cout_tile, plan.spatial_tile,
        plan.tap_unroll)
    assert ws <= 1 << 20


def test_interpret_mode_prefers_fewer_steps():
    """Interpret mode pays per grid step, so the planner unrolls the tap
    loop (fewer, fatter steps); compiled mode caps the unroll at the
    code-size bound."""
    spec = ConvSpec.make(stride=2, padding=0, filter_shape=3)
    x_shape, dy_shape = _shapes(1, 29, 14, 29, 29)
    interp = tiling.plan_tiles("filter_grad", spec, x_shape=x_shape,
                               dy_shape=dy_shape, interpret=True)
    comp = tiling.plan_tiles("filter_grad", spec, x_shape=x_shape,
                             dy_shape=dy_shape, interpret=False)
    assert interp.tap_unroll == 9, interp       # all taps in one step
    assert comp.tap_unroll <= tiling.MAX_TAP_UNROLL_COMPILED, comp


def test_plan_is_deterministic():
    spec = ConvSpec.make(stride=2, padding=1, filter_shape=5, dilation=2)
    x_shape, dy_shape = _shapes(2, 33, 13, 48, 96)
    for op in tiling.OPS:
        a, b = (tiling.plan_tiles(op, spec, x_shape=x_shape,
                                  dy_shape=dy_shape, interpret=True)
                for _ in range(2))
        assert a == b, op


def test_plan_tiles_memoized_with_env_in_key():
    """The analytical `plan_tiles` path is memoized (ops.py re-resolves
    the plan on every conv call -- the steady-state cost must be a dict
    lookup), and the env-derived budget/mode are PART OF THE KEY: an
    `ECOFLOW_VMEM_BUDGET` flip re-plans instead of replaying a winner
    scored against the old constraints."""
    spec = ConvSpec.make(stride=2, padding=1, filter_shape=3)
    x_shape, dy_shape = _shapes(1, 65, 32, 64, 64)
    kw = dict(x_shape=x_shape, dy_shape=dy_shape, interpret=True)
    tiling._planned.cache_clear()
    p1 = tiling.plan_tiles("backward", spec, **kw)
    miss1 = tiling.plan_cache_info().misses
    p2 = tiling.plan_tiles("backward", spec, **kw)
    info = tiling.plan_cache_info()
    assert p1 == p2
    assert info.misses == miss1 and info.hits >= 1, info
    # A different budget is a different key (re-plan, not a cache hit) --
    # plan_tiles resolves the env BEFORE the lookup, so this is exactly
    # the ECOFLOW_VMEM_BUDGET-flip path.
    tiling.plan_tiles("backward", spec, vmem_budget=1 << 22, **kw)
    assert tiling.plan_cache_info().misses == miss1 + 1
    # ... and so is a different ECOFLOW_TILING mode string.
    tiling.plan_tiles("backward", spec, mode="analytical-v2", **kw)
    assert tiling.plan_cache_info().misses == miss1 + 2


def test_unknown_op_rejected():
    spec = ConvSpec.make(stride=1, filter_shape=1)
    with pytest.raises(ValueError, match="unknown op"):
        tiling.plan_tiles("nope", spec, x_shape=(1, 4, 4, 1),
                          dy_shape=(1, 4, 4, 1))


def test_autotune_sweeps_caches_and_persists(tmp_path):
    """Autotune mode sweeps the candidate set through the registered
    runner exactly once per geometry: the winner persists to the JSON
    cache and later calls hit the in-memory / on-disk caches without
    re-running a single candidate."""
    spec = ConvSpec.make(stride=2, padding=0, filter_shape=2)
    x_shape, dy_shape = _shapes(1, 8, 4, 4, 4)
    cache = tmp_path / "tile_cache.json"
    calls = []

    def factory(spec_, x_s, dy_s):
        assert spec_ == spec and x_s == x_shape and dy_s == dy_shape

        def run(plan):
            calls.append(plan)
            return None

        return run

    kw = dict(x_shape=x_shape, dy_shape=dy_shape, mode="autotune",
              runner_factory=factory, tile_cache_path=cache)
    tiling._MEM_CACHE.clear()
    plan = tiling.plan_tiles("filter_grad", spec, **kw)
    assert calls, "autotune never invoked the runner"
    assert plan.source == "autotune"
    n_swept = len(calls)

    # Second call: in-memory cache, no new runner invocations.
    plan2 = tiling.plan_tiles("filter_grad", spec, **kw)
    assert len(calls) == n_swept
    assert (plan2.cin_tile, plan2.cout_tile) == (plan.cin_tile,
                                                 plan.cout_tile)

    # Fresh "process": disk cache only.
    tiling._MEM_CACHE.clear()
    plan3 = tiling.plan_tiles("filter_grad", spec, **kw)
    assert len(calls) == n_swept
    assert plan3.source == "cache"
    assert plan3.cin_tile == plan.cin_tile

    doc = json.loads(cache.read_text())
    assert len(doc) == 1
    (key, rec), = doc.items()
    assert key.startswith("filter_grad|") and "us" in rec
    assert rec["cin_tile"] == plan.cin_tile


def test_autotune_without_runner_falls_back_analytical(tmp_path):
    """No registered runner for an op -> autotune degrades to the
    analytical model instead of failing the conv."""
    spec = ConvSpec.make(stride=1, filter_shape=1)
    saved = dict(tiling._RUNNERS)
    tiling._RUNNERS.clear()
    try:
        plan = tiling.plan_tiles(
            "forward", spec, x_shape=(1, 4, 4, 3), dy_shape=(1, 4, 4, 5),
            mode="autotune", tile_cache_path=tmp_path / "c.json")
    finally:
        tiling._RUNNERS.update(saved)
    assert plan.source == "analytical"


def test_autotune_through_real_kernel(tmp_path):
    """End to end: the filter-grad kernel's registered runner really
    executes the kernel per candidate and the cached winner reproduces
    the reference gradient when used."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ref
    from repro.kernels.dconv_filtergrad import dconv_filter_grad_pallas
    rng = np.random.default_rng(0)
    B, N, K, S, Ci, Co = 1, 7, 2, 2, 3, 4
    O = (N - K) // S + 1
    x_shape, dy_shape = (B, N, N, Ci), (B, O, O, Co)
    spec = ConvSpec.make(stride=S, padding=0, filter_shape=K)
    tiling._MEM_CACHE.clear()
    plan = tiling.plan_tiles("filter_grad", spec, x_shape=x_shape,
                             dy_shape=dy_shape, mode="autotune",
                             tile_cache_path=tmp_path / "c.json")
    assert plan.source == "autotune"
    assert (tmp_path / "c.json").exists()
    x = jnp.asarray(rng.normal(size=x_shape), jnp.float32)
    dy = jnp.asarray(rng.normal(size=dy_shape), jnp.float32)
    dw = dconv_filter_grad_pallas(
        x, dy, stride=(S, S), padding=(0, 0), k=(K, K),
        cin_tile=plan.cin_tile, cout_tile=plan.cout_tile,
        spatial_tile=plan.spatial_tile, tap_unroll=plan.tap_unroll,
        interpret=True)
    want = ref.dconv_filter_grad_ref(x, dy, stride=(S, S),
                                     padding=(0, 0), k=(K, K))
    np.testing.assert_allclose(np.asarray(dw), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Epilogue-aware planning + cache keys (DESIGN.md Sec. 2.8)
# ---------------------------------------------------------------------------

def test_cache_key_includes_epilogue():
    """The autotune cache key carries the epilogue tag: an epilogue
    changes the kernel's block set, so an epilogue-free winner must never
    be replayed for an epilogue-bearing launch (and vice versa)."""
    from repro.core.spec import Epilogue
    spec = ConvSpec.make(stride=2, padding=1, filter_shape=3)
    x_shape, dy_shape = _shapes(1, 9, 5, 4, 8)
    base = tiling._cache_key("backward", spec, x_shape, dy_shape, 4,
                             1 << 23, True, None)
    relu = tiling._cache_key("backward", spec, x_shape, dy_shape, 4,
                             1 << 23, True, Epilogue(activation="relu"))
    brelu = tiling._cache_key("backward", spec, x_shape, dy_shape, 4,
                              1 << 23, True,
                              Epilogue(activation="relu", bias=True))
    assert base.endswith("|ep:none")
    assert relu.endswith("|ep:relu")
    assert brelu.endswith("|ep:b+relu")
    assert len({base, relu, brelu}) == 3


def test_autotune_reads_legacy_keyless_rows(tmp_path):
    """Rows written before the epilogue slot existed (no `|ep:` suffix)
    are still served -- but ONLY for epilogue-free lookups, whose
    candidate set they were actually swept against.  An epilogue-bearing
    lookup must NOT match a legacy row."""
    from repro.core.spec import Epilogue
    spec = ConvSpec.make(stride=2, padding=0, filter_shape=2)
    x_shape, dy_shape = _shapes(1, 8, 4, 4, 4)
    cache = tmp_path / "tile_cache.json"
    key = tiling._cache_key("filter_grad", spec, x_shape, dy_shape, 4,
                            tiling.DEFAULT_VMEM_BUDGET, True, None)
    # A pre-epilogue row predates the |st:/|ep: suffixes entirely.
    pre_strategy, _, tag = key.replace("|st:phase|", "|").rpartition("|ep:")
    legacy_key = pre_strategy
    assert tag == "none"
    legacy_rec = {"cin_tile": 4, "cout_tile": 4, "spatial_tile": 2,
                  "tap_unroll": 1, "phase_unroll": 1,
                  "grid_order": ["cin", "cout", "batch", "spatial", "tap"],
                  "source": "autotune", "us": 1.0}
    cache.write_text(json.dumps({legacy_key: legacy_rec}))

    calls = []

    def factory(spec_, x_s, dy_s, epilogue=None):
        def run(plan):
            calls.append(plan)
            return None
        return run

    kw = dict(x_shape=x_shape, dy_shape=dy_shape, mode="autotune",
              interpret=True, runner_factory=factory,
              tile_cache_path=cache)
    tiling._MEM_CACHE.clear()
    plan = tiling.plan_tiles("filter_grad", spec, **kw)
    assert not calls, "legacy keyless row should have been served"
    assert plan.source == "cache" and plan.spatial_tile == 2

    # An epilogue-bearing lookup misses the legacy row and re-sweeps.
    tiling._MEM_CACHE.clear()
    plan_ep = tiling.plan_tiles("filter_grad", spec,
                                epilogue=Epilogue(activation="relu"), **kw)
    assert calls, "epilogue lookup must not be served a legacy row"
    assert plan_ep.source == "autotune"
    doc = json.loads(cache.read_text())
    assert legacy_key in doc                      # legacy row untouched
    assert any(k.endswith("|ep:relu") for k in doc)


def test_autotune_passes_epilogue_to_runner_factory(tmp_path):
    """Epilogue-aware runner factories receive the descriptor; legacy
    3-arg factories still work for epilogue-free sweeps but are rejected
    (not silently mistimed) when the launch carries an epilogue."""
    from repro.core.spec import Epilogue
    ep = Epilogue(activation="relu", bias=True)
    spec = ConvSpec.make(stride=2, padding=0, filter_shape=2)
    x_shape, dy_shape = _shapes(1, 8, 4, 4, 4)
    seen = []

    def factory(spec_, x_s, dy_s, epilogue=None):
        seen.append(epilogue)

        def run(plan):
            return None
        return run

    kw = dict(x_shape=x_shape, dy_shape=dy_shape, mode="autotune",
              tile_cache_path=tmp_path / "c.json")
    tiling._MEM_CACHE.clear()
    tiling.plan_tiles("filter_grad", spec, epilogue=ep,
                      runner_factory=factory, **kw)
    assert seen == [ep]

    def legacy_factory(spec_, x_s, dy_s):
        def run(plan):
            return None
        return run

    tiling._MEM_CACHE.clear()
    with pytest.raises(TypeError, match="epilogue"):
        tiling.plan_tiles("forward", spec, epilogue=ep,
                          runner_factory=legacy_factory, **kw)


def test_epilogue_shifts_working_set_model():
    """The backward model charges the epilogue's extra blocks: the
    y-mask stream doubles the dy-frame residency and the db output adds
    its accumulator, so a tight budget can force a smaller tile than the
    epilogue-free plan chooses."""
    from repro.core.spec import Epilogue
    spec = ConvSpec.make(stride=2, padding=1, filter_shape=3)
    x_shape, dy_shape = _shapes(1, 65, 33, 64, 64)
    g = tiling._geom("backward", spec, x_shape, dy_shape, 4)
    ep = Epilogue(activation="relu", bias=True)
    ws0, _, _, _ = tiling._MODELS["backward"](g, 64, 64, 33, 1, 1)
    ws1, _, _, _ = tiling._MODELS["backward"](g, 64, 64, 33, 1, 1, ep=ep)
    assert ws1 > ws0
    # ct_backward: z block mirrors the g block.
    g2 = tiling._geom("ct_backward", spec, x_shape, dy_shape, 4)
    ws0, _, _, _ = tiling._MODELS["ct_backward"](g2, 64, 64, 33, 1, 1)
    ws1, _, _, _ = tiling._MODELS["ct_backward"](g2, 64, 64, 33, 1, 1,
                                                 ep=ep)
    assert ws1 > ws0


def test_cache_store_is_atomic_and_leaves_no_temp(tmp_path):
    """The cache publish goes through a same-directory temp file +
    os.replace: after a store the path holds complete, parseable JSON
    and no temp litter remains (the atomic-rename contract concurrent
    autotuners rely on)."""
    cache = tmp_path / "tile_cache.json"
    tiling._store_disk_cache(cache, {"k": {"cin_tile": 4}})
    assert json.loads(cache.read_text()) == {"k": {"cin_tile": 4}}
    assert [p.name for p in tmp_path.iterdir()] == ["tile_cache.json"]
    # overwrite replaces wholesale, again atomically
    tiling._store_disk_cache(cache, {"k2": {"cout_tile": 8}})
    assert json.loads(cache.read_text()) == {"k2": {"cout_tile": 8}}
    assert [p.name for p in tmp_path.iterdir()] == ["tile_cache.json"]


def test_corrupt_cache_file_warns_and_retunes(tmp_path):
    """A truncated/corrupt cache file (pre-atomic-write crash, torn
    copy) must warn and re-tune -- not crash the conv that looked it up
    -- and the re-tuned winner must rewrite the file as valid JSON."""
    spec = ConvSpec.make(stride=2, padding=0, filter_shape=2)
    x_shape, dy_shape = _shapes(1, 8, 4, 4, 4)
    cache = tmp_path / "tile_cache.json"
    cache.write_text('{"filter_grad|truncated-mid-wri')   # torn write
    calls = []

    def factory(spec_, x_s, dy_s):
        def run(plan):
            calls.append(plan)
            return None
        return run

    kw = dict(x_shape=x_shape, dy_shape=dy_shape, mode="autotune",
              runner_factory=factory, tile_cache_path=cache)
    tiling._MEM_CACHE.clear()
    with pytest.warns(RuntimeWarning, match="corrupt autotune tile cache"):
        plan = tiling.plan_tiles("filter_grad", spec, **kw)
    assert calls, "corrupt cache should trigger a fresh sweep"
    assert plan.source == "autotune"
    doc = json.loads(cache.read_text())   # file rewritten, valid again
    assert any(k.startswith("filter_grad|") for k in doc)


def test_malformed_cache_record_warns_and_retunes(tmp_path):
    """A parseable file whose matching ROW is missing required fields is
    equally tolerated: warn, ignore the row, sweep, rewrite."""
    spec = ConvSpec.make(stride=2, padding=0, filter_shape=2)
    x_shape, dy_shape = _shapes(1, 8, 4, 4, 4)
    cache = tmp_path / "tile_cache.json"
    calls = []

    def factory(spec_, x_s, dy_s):
        def run(plan):
            calls.append(plan)
            return None
        return run

    kw = dict(x_shape=x_shape, dy_shape=dy_shape, mode="autotune",
              runner_factory=factory, tile_cache_path=cache)
    tiling._MEM_CACHE.clear()
    good = tiling.plan_tiles("filter_grad", spec, **kw)
    (key, rec), = json.loads(cache.read_text()).items()
    cache.write_text(json.dumps({key: {"us": 1.0}}))   # fields gone
    tiling._MEM_CACHE.clear()
    n = len(calls)
    with pytest.warns(RuntimeWarning, match="malformed autotune tile"):
        plan = tiling.plan_tiles("filter_grad", spec, **kw)
    assert len(calls) > n, "malformed row should re-sweep"
    assert plan.source == "autotune"
    assert plan.cin_tile == good.cin_tile


# ---------------------------------------------------------------------------
# Strategy planner (`plan_strategy`, DESIGN.md Sec. 2.10)
# ---------------------------------------------------------------------------

def test_cache_key_includes_strategy():
    """The strategy segment keys the cache: a phase-swept winner must
    never be replayed for an implicit-GEMM launch, and the `|st:` slot
    sits BEFORE `|ep:` so the epilogue tag keeps its suffix position."""
    spec = ConvSpec.make(stride=2, padding=1, filter_shape=3)
    x_shape, dy_shape = _shapes(1, 9, 5, 4, 8)
    keys = {st: tiling._cache_key("input_grad", spec, x_shape, dy_shape,
                                  4, 1 << 23, True, None, st)
            for st in ("phase", "implicit_gemm", "auto")}
    assert len(set(keys.values())) == 3
    for st, key in keys.items():
        assert f"|st:{st}|" in key
        assert key.endswith("|ep:none")


def test_legacy_rows_served_only_to_phase_lookups():
    """`_legacy_cache_keys`: pre-strategy and pre-epilogue key forms are
    reconstructed ONLY for `st:phase` lookups -- the legacy rows were
    swept against the phase kernels, so an implicit-GEMM (or auto)
    lookup gets no fallback."""
    spec = ConvSpec.make(stride=2, padding=0, filter_shape=3)
    x_shape, dy_shape = _shapes(1, 9, 4, 4, 4)
    phase_key = tiling._cache_key("input_grad", spec, x_shape, dy_shape,
                                  4, 1 << 23, True, None, "phase")
    legacy = tiling._legacy_cache_keys(phase_key)
    assert len(legacy) == 2
    assert legacy[0] == phase_key.replace("|st:phase|", "|")
    assert legacy[1] == legacy[0].rpartition("|ep:")[0]
    for st in ("implicit_gemm", "auto"):
        key = tiling._cache_key("input_grad", spec, x_shape, dy_shape,
                                4, 1 << 23, True, None, st)
        assert tiling._legacy_cache_keys(key) == ()


def test_strategy_env_flip_replans(monkeypatch):
    """Flipping ECOFLOW_STRATEGY re-plans on the next call instead of
    serving the other strategy's memoized plan: the strategy is part of
    the `_planned` lru key, and the returned plan actually differs
    (implicit-GEMM plans carry no phase axis)."""
    spec = ConvSpec.make(stride=2, padding=1, filter_shape=3)
    x_shape, dy_shape = _shapes(2, 9, 5, 16, 32)
    kw = dict(x_shape=x_shape, dy_shape=dy_shape, interpret=True)

    monkeypatch.setenv("ECOFLOW_STRATEGY", "phase")
    st_p, plan_p = tiling.plan_strategy("input_grad", spec, **kw)
    assert st_p == "phase"
    assert plan_p.grid_order == tiling._GRID_ORDERS["input_grad"]

    monkeypatch.setenv("ECOFLOW_STRATEGY", "implicit_gemm")
    st_g, plan_g = tiling.plan_strategy("input_grad", spec, **kw)
    assert st_g == "implicit_gemm"
    assert plan_g.grid_order == \
        tiling._GRID_ORDERS["input_grad:implicit_gemm"]
    assert "phase" not in plan_g.grid_order
    assert plan_g.phase_unroll == 1

    # back to phase: served again (memoized per strategy, not clobbered)
    monkeypatch.setenv("ECOFLOW_STRATEGY", "phase")
    st_p2, plan_p2 = tiling.plan_strategy("input_grad", spec, **kw)
    assert (st_p2, plan_p2) == (st_p, plan_p)

    monkeypatch.setenv("ECOFLOW_STRATEGY", "bogus")
    with pytest.raises(ValueError, match="ECOFLOW_STRATEGY"):
        tiling.plan_strategy("input_grad", spec, **kw)


def test_plan_strategy_unsupported_op_falls_back_to_phase():
    """Ops the implicit-GEMM family does not cover (the fused
    dual-gradient backwards, forward, filter_grad) silently plan phase
    even when implicit_gemm is requested -- the per-op fallback that
    keeps the fused backward launches phase-decomposed."""
    spec = ConvSpec.make(stride=2, padding=1, filter_shape=3)
    x_shape, dy_shape = _shapes(1, 9, 5, 8, 8)
    for op in ("forward", "filter_grad", "backward", "ct_backward"):
        st, plan = tiling.plan_strategy(op, spec, x_shape=x_shape,
                                        dy_shape=dy_shape, interpret=True,
                                        strategy="implicit_gemm")
        assert st == "phase", op
        assert plan.grid_order == tiling._GRID_ORDERS[op]


def test_strategy_cache_roundtrip_and_isolation(tmp_path):
    """Autotune rows are strategy-keyed end to end: a phase row plus
    both legacy forms in the cache must NOT be served to an
    implicit-GEMM lookup (it sweeps its own candidates), and the auto
    race persists ONE `|st:auto` row whose `strategy` field records the
    winner and is replayed as (strategy, plan)."""
    spec = ConvSpec.make(stride=2, padding=0, filter_shape=2)
    x_shape, dy_shape = _shapes(1, 8, 4, 4, 4)
    cache = tmp_path / "tile_cache.json"
    phase_key = tiling._cache_key("input_grad", spec, x_shape, dy_shape,
                                  4, tiling.DEFAULT_VMEM_BUDGET, True,
                                  None, "phase")
    pre_strategy = phase_key.replace("|st:phase|", "|")
    rec = {"cin_tile": 4, "cout_tile": 4, "spatial_tile": 8,
           "tap_unroll": 1, "phase_unroll": 1,
           "grid_order": ["batch", "phase", "cin", "cout", "tap"],
           "source": "autotune", "us": 1.0}
    cache.write_text(json.dumps({
        phase_key: rec, pre_strategy: rec,
        pre_strategy.rpartition("|ep:")[0]: rec}))

    calls = []

    def factory(spec_, x_s, dy_s, epilogue=None):
        def run(plan):
            calls.append(plan)
            return None
        return run

    kw = dict(x_shape=x_shape, dy_shape=dy_shape, mode="autotune",
              interpret=True, tile_cache_path=cache)
    tiling._MEM_CACHE.clear()
    tiling._MEM_STRATEGY.clear()

    st, plan = tiling.plan_strategy("input_grad", spec, strategy="phase",
                                    runner_factory=factory, **kw)
    assert not calls, "phase lookup should be served its cached row"
    assert (st, plan.source) == ("phase", "cache")

    ig_runner = tiling._RUNNERS.get(("input_grad", "implicit_gemm"))
    saved = dict(tiling._RUNNERS)
    tiling._RUNNERS.clear()
    try:
        tiling._RUNNERS[("input_grad", "implicit_gemm")] = factory
        st, plan = tiling.plan_strategy("input_grad", spec,
                                        strategy="implicit_gemm", **kw)
        assert calls, "implicit-GEMM lookup must not be served phase rows"
        assert (st, plan.source) == ("implicit_gemm", "autotune")
        doc = json.loads(cache.read_text())
        ig_key = phase_key.replace("|st:phase|", "|st:implicit_gemm|")
        assert doc[ig_key]["strategy"] == "implicit_gemm"

        # auto race: both runners registered, one |st:auto row persisted
        tiling._RUNNERS[("input_grad", "phase")] = factory
        tiling._MEM_CACHE.clear()
        tiling._MEM_STRATEGY.clear()
        st, plan = tiling.plan_strategy("input_grad", spec,
                                        strategy="auto", **kw)
        assert st in tiling.STRATEGIES
        auto_key = phase_key.replace("|st:phase|", "|st:auto|")
        doc = json.loads(cache.read_text())
        assert doc[auto_key]["strategy"] == st
        # replay from disk: same (strategy, plan) without a sweep
        tiling._MEM_CACHE.clear()
        tiling._MEM_STRATEGY.clear()
        n = len(calls)
        st2, plan2 = tiling.plan_strategy("input_grad", spec,
                                          strategy="auto", **kw)
        assert len(calls) == n
        assert st2 == st and plan2.source == "cache"
        tiles = lambda p: (p.cin_tile, p.cout_tile, p.spatial_tile,
                           p.tap_unroll, p.phase_unroll, p.grid_order)
        assert tiles(plan2) == tiles(plan)
    finally:
        tiling._RUNNERS.clear()
        tiling._RUNNERS.update(saved)
        if ig_runner is not None:
            tiling._RUNNERS[("input_grad", "implicit_gemm")] = ig_runner


def test_analytical_race_crossover_on_bench_geometries():
    """The analytical strategy model reproduces the paper's crossover on
    the Table 5 / Table 7 geometries: the high-waste AlexNet S=4 stem
    plans phase decomposition while at least one S<=2 / dilated layer
    plans implicit-GEMM -- in BOTH execution modes."""
    from repro.core import dataflow_sim as ds
    layers = {L.name: L for L in (list(ds.TABLE5_LAYERS)
                                  + list(ds.TABLE7_GAN_LAYERS)
                                  + list(ds.DILATED_LAYERS))}

    def race(L, interpret):
        spec = ConvSpec.make(stride=L.stride, padding=L.padding,
                             filter_shape=L.k, dilation=L.dilation)
        st, _ = tiling.plan_strategy(
            "input_grad", spec,
            x_shape=(L.batch, L.n_in, L.n_in, L.c_in),
            dy_shape=(L.batch, L.n_out, L.n_out, L.m),
            interpret=interpret, strategy="auto")
        return st

    for interpret in (True, False):
        picks = {name: race(L, interpret) for name, L in layers.items()}
        assert picks["alexnet-CONV1"] == "phase", picks
        assert "implicit_gemm" in picks.values(), picks


_RELU = Epilogue(activation="relu")
_K4S2 = ConvSpec.make(stride=2, padding=1, filter_shape=4)


@pytest.mark.parametrize("name,op,spec,x_shape,dy_shape,ep,want", [
    # dcgan32's generator at slot batch 64: (cin, cout, spatial, taps,
    # phases)
    ("t1", "input_grad", _K4S2, (64, 8, 8, 256), (64, 4, 4, 512), _RELU,
     (256, 256, 4, 2, 4)),
    ("t2", "input_grad", _K4S2, (64, 16, 16, 128), (64, 8, 8, 256), _RELU,
     (128, 256, 8, 4, 4)),
    ("t3", "input_grad", _K4S2, (64, 32, 32, 3), (64, 16, 16, 128),
     Epilogue(activation="tanh"), (3, 128, 16, 4, 4)),
] + [
    # the ASPP head's three atrous branches at slot batch 8
    (f"rate{r}", "forward",
     ConvSpec.make(stride=1, padding=r, filter_shape=3, dilation=r),
     (8, 33, 33, 2048), (8, 33, 33, 256), _RELU,
     (128, 256, 33, 9 if r < 18 else 3, 1)) for r in (6, 12, 18)])
def test_benchmark_cells_keep_their_plans(name, op, spec, x_shape, dy_shape,
                                          ep, want):
    """The compiled plans of the benchmark cells' launches, as they were
    before the strided packing and the phase kernel's row bands."""
    strategy, plan = tiling.plan_strategy(op, spec, x_shape=x_shape,
                                          dy_shape=dy_shape,
                                          interpret=False, epilogue=ep)
    assert strategy == "phase"
    assert (plan.cin_tile, plan.cout_tile, plan.spatial_tile,
            plan.tap_unroll, plan.phase_unroll) == want


@pytest.mark.parametrize("o,cin,cout,bands", [
    (128, 128, 3, 26), (64, 256, 64, 8), (32, 512, 128, 2),
    (16, 1024, 256, 1)])
def test_phase_planes_past_the_row_cap_run_in_bands(o, cin, cout, bands):
    """U-Net-256's last transposed convs: compiled, a phase plane whose
    tap window would pass `MAX_WINDOW_ROWS_COMPILED` sites runs in row
    bands under it; below the cap, and in interpret mode, planes stay
    whole."""
    kw = dict(x_shape=(16, 2 * o, 2 * o, cout), dy_shape=(16, o, o, cin))
    plan = tiling.plan_tiles("input_grad", _K4S2, interpret=False, **kw)
    ho = o + 1                                   # phase-plane rows
    band, n = tiling.phase_bands(o, ho, plan.spatial_tile)
    assert n == bands and n * band >= ho > (n - 1) * band
    assert band * ho <= tiling.MAX_WINDOW_ROWS_COMPILED
    assert tiling.plan_tiles("input_grad", _K4S2, interpret=True,
                             **kw).spatial_tile == o


def test_forward_plans_see_the_packed_problem():
    """A strided forward is planned as the stride-1 space-to-depth
    problem it launches: the 3-channel stem tiles 12 packed channels and
    unrolls at most its 2 x 2 taps."""
    plan = tiling.plan_tiles("forward", _K4S2, x_shape=(16, 256, 256, 3),
                             dy_shape=(16, 128, 128, 64), interpret=False)
    assert plan.cin_tile == 12 and plan.tap_unroll in (2, 4)
