"""Ahead-of-time compiles of the main conv path for a TPU v5e.

Nothing runs: each test lowers a kernel (or a whole pallas GAN train
step) with `interpret=False` against a described `v5e:2x2` topology and
lets Mosaic and XLA compile it, which raises what the chip's compiler
would refuse -- unaligned blocks, value-level gathers, VMEM overruns.
Shapes are the GAN's default widths (z_dim 64, base 64, 32x32 images,
batch 128) and the default ASPP head at 128x128x3.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and the test workers all import
this file.  Compiled mode is steered through the one interpret switch,
`kernels.ops.interpret_mode`, so the planner and the kernels see exactly
what they would see on the chip.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core.spec import ConvSpec, Epilogue
from repro.kernels import ops, tiling

B = 128
LEAKY = Epilogue(activation="leaky_relu", slope=0.2)
LEAKY_BIAS = Epilogue(activation="leaky_relu", slope=0.2, bias=True)
RELU = Epilogue(activation="relu")
K4S2 = dict(stride=(2, 2), padding=(1, 1))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        if "TPU_LOG_DIR" not in os.environ:
            mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means no TPU lib
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_mode(monkeypatch):
    monkeypatch.setattr(ops, "interpret_mode", lambda: False)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    print(compiled.memory_analysis())
    return text


# name -> (wrapper call, operand shapes); the discriminator's c2 layer
# (16x16x32 -> 8x8x64) and the generator's t2 layer (8x8x64 -> 16x16x32),
# both K4 S2 P1, and one ASPP branch (rate 2) at the serving size.
_CASES = {
    "strided_forward": (
        lambda x, w, b: ops.dconv_forward(x, w, dilation=(1, 1), bias=b,
                                          epilogue=LEAKY_BIAS,
                                          **K4S2),
        ((B, 16, 16, 32), (4, 4, 32, 64), (64,))),
    "dilated_forward": (
        lambda x, w: ops.dconv_forward(x, w, stride=(1, 1), padding=(2, 2),
                                       dilation=(2, 2), epilogue=RELU),
        ((4, 128, 128, 3), (3, 3, 3, 16))),
    "tconv_phase": (
        lambda dy, w: ops.tconv_phase(dy, w, n_out=(16, 16), epilogue=RELU,
                                      strategy="phase", **K4S2),
        ((B, 8, 8, 64), (4, 4, 32, 64))),
    "implicit_gemm": (
        lambda dy, w: ops.tconv_phase(dy, w, n_out=(16, 16), epilogue=RELU,
                                      strategy="implicit_gemm", **K4S2),
        ((B, 8, 8, 64), (4, 4, 32, 64))),
    "backward": (
        lambda x, dy, w, y: ops.conv_backward(x, dy, w, n_out=(16, 16), y=y,
                                              epilogue=LEAKY, **K4S2),
        ((B, 16, 16, 32), (B, 8, 8, 64), (4, 4, 32, 64), (B, 8, 8, 64))),
    "ct_backward": (
        lambda g, dy, w, z: ops.tconv_backward(g, dy, w, z=z, epilogue=RELU,
                                               **K4S2),
        ((B, 16, 16, 32), (B, 8, 8, 64), (4, 4, 32, 64), (B, 16, 16, 32))),
    "filter_grad": (
        lambda x, dy: ops.dconv_filter_grad(x, dy, k=(4, 4), **K4S2),
        ((B, 16, 16, 32), (B, 8, 8, 64))),
}


@pytest.mark.parametrize("name", sorted(_CASES))
def test_kernel_compiles_for_v5e(name, one_chip, compiled_mode):
    fn, shapes = _CASES[name]
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
            for s in shapes]
    _compile(fn, *args)


def test_implicit_gemm_excluded_past_unroll_cap():
    """Compiled implicit-GEMM needs every tap unrolled; past the cap the
    planner never picks it, whatever strategy is asked for."""
    spec = ConvSpec.make(stride=4, padding=5, filter_shape=11)
    assert 11 * 11 > tiling.MAX_TAP_UNROLL_COMPILED
    for strategy in ("auto", "implicit_gemm"):
        picked, _ = tiling.plan_strategy(
            "input_grad", spec, x_shape=(8, 59, 59, 3),
            dy_shape=(8, 15, 15, 96), interpret=False, strategy=strategy)
        assert picked == "phase", strategy


def test_gan_train_step_compiles_for_v5e(one_chip, compiled_mode):
    """One whole guarded pallas GAN step (G and D forward and backward):
    every pallas_call of its jaxpr is compiled, none interpreted, and
    lowers to a tpu_custom_call."""
    from conftest import walk_eqns

    from repro.train.conv_trainer import ConvTrainer, ConvTrainerConfig

    tr = ConvTrainer(ConvTrainerConfig(workload="gan", backend="pallas",
                                       z_dim=64, base=64, image=32,
                                       batch=B))
    put = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                         sharding=one_chip)
    state = jax.tree.map(put, jax.eval_shape(tr.init_state))
    data = (put(jax.ShapeDtypeStruct((B, 64), jnp.float32)),
            put(jax.ShapeDtypeStruct((B, 32, 32, 3), jnp.float32)))
    lr = put(jax.ShapeDtypeStruct((), jnp.float32))
    step = tr.build_step(guarded=True)
    calls = [e for e in walk_eqns(jax.make_jaxpr(step)(state, data,
                                                       lr).jaxpr)
             if e.primitive.name == "pallas_call"]
    assert calls and not any(e.params["interpret"] for e in calls)
    compiled = jax.jit(step).lower(state, data, lr).compile()
    # XLA's CSE merges the forward launches the two losses repeat.
    n = compiled.as_text().count('custom_call_target="tpu_custom_call"')
    assert 0 < n <= len(calls), (n, len(calls))
    print(compiled.memory_analysis())
