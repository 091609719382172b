"""CPU rehearsal of the training driver on a tiny GAN (Pallas kernels in
interpret mode), and of the reference's rounded products."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, reference
from bench.drivers import train

TINY = dict(harness.load_model("dcgan").TINY, batch=8)
# float32 on the CPU: the program and the reference differ only by the
# order of float32 sums.
LIMITS = {"loss_err": 1e-4, "grad_err": 1e-3, "change_err": 1e-3}


def tiny_ctx(seconds: float = 1.0) -> harness.Context:
    cell = {"config": "tiny", "traffic": "steps", "chips": 1, "lr": 0.05,
            "check": dict(LIMITS)}
    config = {"model": TINY, "numerics": {"matmul_operands": "float32"}}
    return harness.Context(
        name="tiny-train", cell=cell, config=config, traffic={},
        seed=2 ** 31 + 5, seconds=seconds, trace=False,
        t_start=time.monotonic(),
        peaks={"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        devices=jax.devices()[:1])


def test_train_driver_runs_and_is_correct():
    out = train.run(tiny_ctx())
    assert out.correct, out.checks
    assert out.failed == 0 and out.attempted >= 1
    assert out.end_to_end["train_throughput"] > 0
    assert out.checks["window_repeats_setup"][0] == 0.0
    for k, lim in LIMITS.items():
        assert out.checks[k][0] < lim


def _break_step(monkeypatch, how: str):
    from repro.train.conv_trainer import ConvTrainer
    build = ConvTrainer.build_step

    def broken(self, *, guarded):
        fn = build(self, guarded=guarded)

        def step(state, data, lr):
            if how == "unchanged":
                _, metrics, fin = fn(state, data, lr)
                return state, metrics, fin
            half = tuple(a[:a.shape[0] // 2] for a in data)
            return fn(state, half, lr)
        return step
    monkeypatch.setattr(ConvTrainer, "build_step", broken)


@pytest.mark.parametrize("how", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(monkeypatch, how):
    """A step that returns its state unchanged, or that leaves out half
    of the batch and takes the mean over the rest, fails the check."""
    _break_step(monkeypatch, how)
    out = train.run(tiny_ctx(seconds=0.2))
    assert not out.correct, out.checks


def test_rounded_products_round_both_directions():
    """Forward and both gradients of a rounded product take bfloat16-
    rounded operands and are otherwise exact."""
    rd = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    k1, k2, k3 = jax.random.split(jax.random.key(0), 3)
    a = jax.random.normal(k1, (4, 6))
    b = jax.random.normal(k2, (6, 5))
    ct = jax.random.normal(k3, (4, 5))
    hi = jax.lax.Precision.HIGHEST
    num = reference.Numerics("bfloat16")
    y, vjp = jax.vjp(lambda a, b: reference.dot(a, b, num=num), a, b)
    da, db = vjp(ct)
    np.testing.assert_allclose(y, jnp.dot(rd(a), rd(b), precision=hi),
                               rtol=1e-6)
    np.testing.assert_allclose(da, jnp.dot(rd(ct), rd(b).T, precision=hi),
                               rtol=1e-6)
    np.testing.assert_allclose(db, jnp.dot(rd(a).T, rd(ct), precision=hi),
                               rtol=1e-6)


def test_reference_tconv_is_the_conv_input_gradient():
    """The reference's transposed conv equals the adjoint of its direct
    conv: <tconv(y, w), x> == <y, conv(x, w)>."""
    k1, k2, k3 = jax.random.split(jax.random.key(1), 3)
    x = jax.random.normal(k1, (2, 8, 8, 3))
    w = jax.random.normal(k2, (4, 4, 3, 5))
    y = jax.random.normal(k3, (2, 4, 4, 5))
    lhs = jnp.vdot(reference.tconv(y, w, 2, 1), x)
    rhs = jnp.vdot(y, reference.conv(x, w, 2, 1))
    assert float(lhs) == pytest.approx(float(rhs), rel=1e-4)
