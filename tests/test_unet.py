"""pix2pix's U-Net generator (`models/unet.py`) against a plain float32
`jax.numpy` reference of the published equations, directly and served
through `ConvServeEngine`; and its planning warm-up against the launches
it makes."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from conftest import assert_allclose, count_pallas_calls
from repro.kernels import tiling
from repro.models import unet
from repro.serve.conv_engine import ConvRequest, ConvServeEngine

N_DOWNS, NGF, IMG = 5, 4, (32, 32, 3)
DN = ("NHWC", "HWIO", "NHWC")


@pytest.fixture(scope="module")
def params():
    return unet.unet_init(jax.random.PRNGKey(3), in_ch=3, out_ch=3,
                          num_downs=N_DOWNS, ngf=NGF)


def images(n, seed=0):
    return jax.random.uniform(jax.random.PRNGKey(seed), (n,) + IMG,
                              jnp.float32, -1.0, 1.0)


def _conv(x, w):
    return lax.conv_general_dilated(x, w, (2, 2), [(1, 1)] * 2,
                                    dimension_numbers=DN)


def _tconv(x, w):
    """The input gradient of the 4x4 stride-2 conv with filter `w`
    (upsampled side first), applied to `x`."""
    b, h, ww, _ = x.shape
    zero = jnp.zeros((b, 2 * h, 2 * ww, w.shape[2]), x.dtype)
    return jax.vjp(lambda a: _conv(a, w), zero)[1](x)[0]


def reference(params, x):
    """The equations of `models/unet.py`'s docstring, one image batch at
    a time, in float32 with no kernel: h1 = conv(x), h_k = conv(LReLU(
    h_{k-1})) (+ folded norm), u_n = tconv(ReLU(h_n)), u_k =
    tconv(ReLU(cat(h_k, u_{k+1}))), out = tanh(u_1)."""
    n = N_DOWNS
    hs = [_conv(x, params["down1"]["w"])]
    for k in range(2, n + 1):
        p = params[f"down{k}"]
        h = _conv(jax.nn.leaky_relu(hs[-1], 0.2), p["w"])
        hs.append(h + p["b"] if "b" in p else h)
    u = jax.nn.relu(hs[-1])
    for k in range(n, 0, -1):
        if k < n:
            u = jax.nn.relu(jnp.concatenate([hs[k - 1], u], axis=-1))
        u = _tconv(u, params[f"up{k}"]["w"]) + params[f"up{k}"]["b"]
    return jnp.tanh(u)


def test_layout_and_widths(params):
    assert set(params) == {f"down{k}" for k in range(1, 6)} | {
        f"up{k}" for k in range(1, 6)}
    # no bias where the authors build none: the two encoder convs
    # without a norm
    assert "b" not in params["down1"] and "b" not in params["down5"]
    assert params["down4"]["w"].shape == (4, 4, 16, 32)
    assert params["up5"]["w"].shape == (4, 4, 32, 32)     # innermost
    assert params["up4"]["w"].shape == (4, 4, 16, 64)     # skip-joined
    assert params["up1"]["w"].shape == (4, 4, 3, 8)
    assert unet.unet_widths(in_ch=3, num_downs=8, ngf=64) == [
        3, 64, 128, 256, 512, 512, 512, 512, 512]


def test_fold_batch_norm_is_the_inference_norm():
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (2, 8, 8, 3))
    w = jax.random.normal(jax.random.fold_in(key, 1), (4, 4, 3, 5))
    g, b, m = jax.random.normal(jax.random.fold_in(key, 2), (3, 5))
    v = 1.0 + jnp.abs(jax.random.normal(jax.random.fold_in(key, 3), (5,)))
    with jax.default_matmul_precision("highest"):
        want = g * (_conv(x, w) - m) / jnp.sqrt(v + unet.BN_EPS) + b
        wf, bf = unet.fold_batch_norm(w, g, b, m, v, axis=3)
        assert_allclose(_conv(x, wf) + bf, want, rtol=1e-5, atol=1e-5)


def test_pallas_matches_the_plain_reference(params):
    x = images(2)
    with jax.default_matmul_precision("highest"):
        want = reference(params, x)
        got = unet.unet_apply(params, x, backend="pallas")
    assert got.shape == (2,) + IMG
    assert float(jnp.std(want)) > 0.05          # every level contributes
    assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_every_conv_is_one_pallas_launch(params):
    x = images(2)
    assert count_pallas_calls(
        lambda a: unet.unet_apply(params, a, backend="pallas"),
        x) == 2 * N_DOWNS


@pytest.mark.parametrize("cohort", [1, 3])
def test_engine_serves_unet_as_the_reference(params, cohort):
    """A cohort of one and a full slot batch, answered as the plain
    reference computes each image alone."""
    eng = ConvServeEngine(unet_params=params, slot_batch=3, queue_limit=8,
                          ladder=("pallas",))
    x = np.asarray(images(cohort, seed=cohort))
    res = eng.serve([ConvRequest(i, "unet", x[i]) for i in range(cohort)])
    assert sorted(res) == list(range(cohort))
    assert eng.stats["launches"] == 1 and eng.stats["failures"] == 0
    with jax.default_matmul_precision("highest"):
        for i in range(cohort):
            want = reference(params, x[i:i + 1])[0]
            assert_allclose(res[i], want, rtol=1e-4, atol=1e-4)


def test_plan_requests_are_the_launches_it_makes(params, monkeypatch):
    """Warm-up plans exactly the (op, geometry, epilogue) of each launch
    `unet_apply` makes, in order; after warm-up a launch compiles
    nothing."""
    made = []
    plan_tiles, plan_strategy = tiling.plan_tiles, tiling.plan_strategy

    def spy(real):
        def f(op, spec, *, x_shape, dy_shape, epilogue=None, **kw):
            made.append((op, spec, tuple(x_shape), tuple(dy_shape),
                         epilogue))
            return real(op, spec, x_shape=x_shape, dy_shape=dy_shape,
                        epilogue=epilogue, **kw)
        return f

    monkeypatch.setattr(tiling, "plan_tiles", spy(plan_tiles))
    monkeypatch.setattr(tiling, "plan_strategy", spy(plan_strategy))
    x = images(3)
    jax.make_jaxpr(lambda a: unet.unet_apply(params, a,
                                             backend="pallas"))(x)
    assert made == unet.unet_plan_requests(params, x.shape)

    eng = ConvServeEngine(unet_params=params, slot_batch=3, queue_limit=8,
                          ladder=("pallas",))
    summary = eng.warmup([("unet", IMG)], compile=True)
    assert summary["plans"] == 2 * N_DOWNS
    key = (("unet", IMG), "pallas")
    compiled = eng._jit_cache[key]._cache_size()
    assert len(eng.serve([ConvRequest(0, "unet", np.asarray(x[0]))])) == 1
    assert set(eng._jit_cache) == {key}
    assert eng._jit_cache[key]._cache_size() == compiled
