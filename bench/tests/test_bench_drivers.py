"""CPU rehearsal of the benchmark's drivers, through the function seam
(`driver.run(ctx)`), on tiny configurations with the Pallas kernels in
interpret mode: the timed path runs end to end and its answers are
checked against the reference; the control, and the timed path broken
underneath, come out as not correct.  Every model kind found in
`bench/models/` is rehearsed at its module's `TINY` size."""
from __future__ import annotations

import shutil
import time

import jax
import numpy as np
import pytest

from bench import harness, serving
from bench.drivers import serve_closed, serve_open

# Served kinds: the modules under `MODELS` that give a request kind
# (a name starting with `_` is a shared helper, not a kind).
KINDS = sorted(p.stem for p in harness.MODELS.glob("[!_]*.py")
               if hasattr(harness.load_model(p.stem), "REQUEST_KIND"))
OPEN = {"loop": "open", "rate_per_s": 12.0, "payload_pool": 6}
CLOSED = {"loop": "closed", "outstanding": 8, "payload_pool": 16}
# On the CPU the kernels' float32 products are exact, so the program and
# the reference differ only by the order of float32 sums.
LIMIT = 1e-4


def tiny_ctx(kind: str, traffic: dict, *, seconds: float = 1.0,
             seed: int = 2 ** 31 + 3) -> harness.Context:
    model = dict(harness.load_model(kind).TINY, kind=kind)
    cell = {"config": f"tiny-{kind}", "traffic": "tiny", "chips": 1,
            "engine": {"slot_batch": 4, "queue_limit": 16,
                       "ladder": ["pallas"]},
            "check": {"share": 0.5, "out_err": LIMIT, "out_rms_err": LIMIT}}
    config = {"model": model,
              "numerics": {"matmul_operands": "float32"}}
    return harness.Context(
        name=f"tiny-{kind}", cell=cell, config=config, traffic=traffic,
        seed=seed, seconds=seconds, trace=False, t_start=time.monotonic(),
        peaks={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        devices=jax.devices()[:1])


@pytest.mark.parametrize("kind", KINDS)
def test_open_loop_runs_and_is_correct(kind):
    out = serve_open.run(tiny_ctx(kind, OPEN))
    assert out.correct, out.checks
    assert out.attempted == 12 and out.failed == 0
    assert out.end_to_end["serve_p95_ms"] > 0
    assert out.end_to_end["setup_s"] > 0
    assert 0 <= out.checks["out_err"][0] < LIMIT
    assert out.layer["launches"] >= 3
    assert out.layer["answered"] == 12


@pytest.mark.parametrize("kind", KINDS)
def test_closed_loop_runs_and_is_correct(kind):
    out = serve_closed.run(tiny_ctx(kind, CLOSED, seconds=0.5))
    assert out.correct, out.checks
    assert out.attempted >= 4 and out.failed == 0
    assert out.attempted % 4 == 0
    assert out.end_to_end["serve_throughput"] > 0
    assert out.layer["stats"]["completed"] == out.attempted


@pytest.mark.parametrize("kind", KINDS)
def test_control_is_not_correct(kind):
    """The reference computed in bfloat16, in the program's place, reads
    far above the limit the program is held to."""
    ctx = tiny_ctx(kind, OPEN)
    assert_control_fails(ctx)


def assert_control_fails(ctx):
    model = ctx.config["model"]
    pool = np.asarray(harness.model_of(ctx).payload_pool(model, ctx.seed, 6))
    order = np.arange(8) % 6
    errs = serving.control_error(ctx, list(range(8)),
                                 serving.payload_fn(pool, order))
    assert min(errs.values()) > 10 * LIMIT, errs
    assert not serving.verdict(serving.checks(ctx, errs, 0))


def test_a_new_kind_needs_only_its_module(monkeypatch, tmp_path):
    """A kind whose one file is its module under `MODELS` is served
    through the open loop and held to its control, with no other file
    of the benchmark naming it."""
    kind = "aspp_copy"
    shutil.copy(harness.MODELS / "aspp.py", tmp_path / f"{kind}.py")
    monkeypatch.setattr(harness, "MODELS", tmp_path)
    ctx = tiny_ctx(kind, OPEN)
    out = serve_open.run(ctx)
    assert out.correct, out.checks
    assert out.layer["answered"] == 12 and out.failed == 0
    assert_control_fails(ctx)


def _break_engine(monkeypatch, how: str):
    """Break the timed path under the driver: alter one answer where the
    engine produces it, or leave half of each cohort unanswered."""
    from repro.serve import conv_engine
    launch = conv_engine.ConvServeEngine._launch

    def broken(self, bucket, cohort):
        out = launch(self, bucket, cohort)
        if how == "altered":
            out = out.copy()
            out[0].flat[0] += 0.5
        return out

    run = conv_engine.ConvServeEngine.run

    def half(self):
        return {j: v for j, v in run(self).items() if j % 2 == 0}

    if how == "altered":
        monkeypatch.setattr(conv_engine.ConvServeEngine, "_launch", broken)
    else:
        monkeypatch.setattr(conv_engine.ConvServeEngine, "run", half)


@pytest.mark.parametrize("compared", [("out_err", "out_rms_err"),
                                      ("out_rms_err",)])
@pytest.mark.parametrize("how", ["altered", "half_unanswered"])
@pytest.mark.parametrize("driver,traffic,seconds", [
    (serve_open, OPEN, 1.0), (serve_closed, CLOSED, 0.5)])
def test_broken_timed_path_is_not_correct(monkeypatch, how, driver,
                                          traffic, seconds, compared):
    """Also where the cell compares the relative 2-norm alone, as
    `dcgan32-gen` does."""
    ctx = tiny_ctx("dcgan", traffic, seconds=seconds)
    # every answer sampled, so that an altered one is always compared
    ctx.cell["check"]["share"] = 1.0
    for k in {"out_err", "out_rms_err"} - set(compared):
        del ctx.cell["check"][k]
    _break_engine(monkeypatch, how)
    out = driver.run(ctx)
    assert not out.correct, out.checks
    assert set(out.checks) == set(compared) | {"unanswered"}
