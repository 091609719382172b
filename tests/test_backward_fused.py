"""Fused dual-gradient backward (`kernels/dconv_backward.py`): parity of
the single-launch (dx, dW) / (ddy, dW) pairs against `jax.grad` of
`lax.conv_general_dilated`, over stride x dilation x ragged channels x
B > 1 -- plus the structural pins of the fusion: exactly ONE
`pallas_call` per conv backward on the `pallas` backend, BOTH outputs
emitted by that same launch, and no duplicated dy-shaped intermediate
anywhere in the traced jaxpr (the error map is fetched once).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ecoflow
from repro.core.conv import ecoflow_conv, ecoflow_conv_transpose
from repro.core.spec import ConvSpec, resolve_backend
from repro.kernels import ops
from repro.kernels.dconv_backward import (conv_backward_pallas,
                                          tconv_backward_pallas)

from conftest import (assert_allclose, count_pallas_calls, pallas_grids,
                      pallas_block_shapes, walk_eqns)

BACKENDS = ["reference", "xla_zero_free", "pallas"]

# (name, B, N, K, S, P, D, Ci, Co): stride x dilation x ragged channels
# x batch > 1 -- the parity grid of the fused backward.
BACKWARD_GRID = [
    ("s1",            2, 8,  3, 1, 1, 1, 3,  4),
    ("s2",            2, 9,  3, 2, 0, 1, 4,  4),
    ("s2_pad",        2, 9,  3, 2, 1, 1, 3,  5),
    ("s2_ragged",     2, 9,  3, 2, 1, 1, 29, 21),
    ("s3_k4",         1, 13, 4, 3, 0, 1, 2,  5),
    ("s4_klt_s",      1, 12, 2, 4, 0, 1, 5,  5),   # K < S: empty phases
    ("s2_nonexact",   2, 10, 3, 2, 0, 1, 3,  4),   # tail rows ignored
    ("s1_d2_atrous",  2, 11, 3, 1, 2, 2, 3,  3),
    ("s2_d2",         2, 14, 3, 2, 1, 2, 3,  2),   # gcd(S, D) = 2
    ("s3_d2_coprime", 1, 14, 3, 3, 0, 2, 2,  3),
    ("ragged_cin_gt_tile", 1, 7, 3, 2, 1, 1, 130, 3),
]


def _ref_grads(x, w, S, P, D, dy):
    """(dx, dw) from jax.vjp of the plain (rhs-dilated) lax conv."""
    f = lambda x_, w_: jax.lax.conv_general_dilated(
        x_, w_, (S, S), [(P, P), (P, P)], rhs_dilation=(D, D),
        dimension_numbers=ecoflow.DN)
    _, vjp = jax.vjp(f, x, w)
    return vjp(dy)


def _case(rng, B, N, K, S, P, D, Ci, Co):
    k_eff = D * (K - 1) + 1
    O = (N + 2 * P - k_eff) // S + 1
    x = jnp.asarray(rng.normal(size=(B, N, N, Ci)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
    return x, w, dy


# ---------------------------------------------------------------------------
# parity: fused backward == jax.grad of the plain conv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,B,N,K,S,P,D,Ci,Co", BACKWARD_GRID)
def test_fused_backward_parity_grid(rng, name, B, N, K, S, P, D, Ci, Co):
    x, w, dy = _case(rng, B, N, K, S, P, D, Ci, Co)
    dx_ref, dw_ref = _ref_grads(x, w, S, P, D, dy)
    dx, dw = ops.conv_backward(x, dy, w, stride=(S, S), padding=(P, P),
                               n_out=(N, N), dilation=(D, D))
    assert dx.shape == x.shape and dw.shape == w.shape
    assert_allclose(dx, dx_ref, rtol=2e-4, atol=2e-4, err_msg=f"{name} dx")
    assert_allclose(dw, dw_ref, rtol=2e-4, atol=2e-4, err_msg=f"{name} dw")


@pytest.mark.parametrize("backend", BACKENDS)
def test_backward_method_all_backends(rng, backend):
    """`ConvBackend.backward` (fused on pallas, two-launch composition on
    reference/xla_zero_free) agrees with jax.grad of the plain conv."""
    B, N, K, S, P, D, Ci, Co = 2, 9, 3, 2, 1, 1, 3, 4
    x, w, dy = _case(rng, B, N, K, S, P, D, Ci, Co)
    spec = ConvSpec.make(stride=S, padding=P, filter_shape=K, dilation=D)
    dx, dw = resolve_backend(backend).backward(x, dy, w, spec, (N, N))
    dx_ref, dw_ref = _ref_grads(x, w, S, P, D, dy)
    assert_allclose(dx, dx_ref, rtol=2e-4, atol=2e-4,
                    err_msg=f"{backend} dx")
    assert_allclose(dw, dw_ref, rtol=2e-4, atol=2e-4,
                    err_msg=f"{backend} dw")


RAGGED_TILE_SWEEP = [
    # (B, N, K, S, P, D, Ci, Co, ci_t, co_t, u, pu): pinned tilings with
    # ragged remainders, multiple Cout tiles, and partial phase/tap
    # unrolls (the traced-slot kernel path with masked dW accumulation).
    (2, 9, 3, 2, 0, 1, 5, 20, 4, 8, 1, 1),
    (2, 9, 3, 2, 0, 1, 5, 20, 4, 8, 2, 2),
    (3, 9, 3, 2, 1, 1, 13, 7, 8, 4, 4, 1),
    (2, 14, 3, 2, 1, 2, 3, 5, 2, 2, 1, 1),    # strided + dilated, traced
    (1, 23, 11, 4, 2, 1, 3, 5, 2, 4, 3, 2),   # big filter, ragged phases
]


@pytest.mark.parametrize("B,N,K,S,P,D,Ci,Co,ci_t,co_t,u,pu",
                         RAGGED_TILE_SWEEP)
def test_fused_backward_ragged_tiles(rng, B, N, K, S, P, D, Ci, Co, ci_t,
                                     co_t, u, pu):
    x, w, dy = _case(rng, B, N, K, S, P, D, Ci, Co)
    dx, dw = conv_backward_pallas(
        x, dy, w, stride=(S, S), padding=(P, P), n_out=(N, N),
        dilation=(D, D), cin_tile=ci_t, cout_tile=co_t, tap_unroll=u,
        phase_unroll=pu, interpret=True)
    dx_ref, dw_ref = _ref_grads(x, w, S, P, D, dy)
    assert_allclose(dx, dx_ref, rtol=2e-4, atol=2e-4)
    assert_allclose(dw, dw_ref, rtol=2e-4, atol=2e-4)


def test_fused_backward_bf16(rng):
    B, N, K, S, Ci, Co = 2, 9, 3, 2, 4, 4
    O = (N - K) // S + 1
    x = jnp.asarray(rng.normal(size=(B, N, N, Ci)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.bfloat16)
    dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.bfloat16)
    dx, dw = conv_backward_pallas(x, dy, w, stride=(S, S), padding=(0, 0),
                                  n_out=(N, N), interpret=True)
    assert dx.dtype == jnp.bfloat16 and dw.dtype == jnp.bfloat16
    dx_ref, dw_ref = _ref_grads(x.astype(jnp.float32),
                                w.astype(jnp.float32), S, 0, 1,
                                dy.astype(jnp.float32))
    assert_allclose(dx, dx_ref, rtol=5e-2, atol=5e-2)
    assert_allclose(dw, dw_ref, rtol=5e-2, atol=5e-2)


def test_fused_backward_rejects_inconsistent_geometry(rng):
    x, w, dy = _case(rng, 1, 9, 3, 2, 0, 1, 3, 4)
    with pytest.raises(ValueError, match="inconsistent"):
        conv_backward_pallas(x, dy[:, :-1], w, stride=(2, 2),
                             padding=(0, 0), interpret=True)


# ---------------------------------------------------------------------------
# parity: fused transposed-conv backward (the GAN generator layer)
# ---------------------------------------------------------------------------

CT_GRID = [
    # (name, B, O, K, S, P, D, Ci, Co)
    ("gan_gen",     2, 8, 4, 2, 1, 1, 8, 16),
    ("s2_ragged",   2, 5, 3, 2, 0, 1, 29, 21),
    ("s3",          1, 6, 4, 3, 0, 1, 3, 5),
    ("s1_d2",       2, 6, 3, 1, 2, 2, 3, 3),
    ("s2_d2",       2, 5, 3, 2, 1, 2, 2, 3),
]


@pytest.mark.parametrize("name,B,O,K,S,P,D,Ci,Co", CT_GRID)
def test_fused_ct_backward_parity_grid(rng, name, B, O, K, S, P, D, Ci,
                                       Co):
    """(ddy, dW) of the transposed conv from one launch == jax.grad of
    the standalone transposed conv through the reference backend."""
    spec = ConvSpec.make(stride=S, padding=P, filter_shape=K, dilation=D)
    n = spec.input_size((O, O))[0]
    dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(B, n, n, Ci)), jnp.float32)

    def loss(dy_, w_, backend):
        z = ecoflow_conv_transpose(dy_, w_, S, P, n_out=(n, n),
                                   backend=backend, dilation=D)
        return jnp.vdot(z, g)

    ddy, dw = jax.grad(loss, argnums=(0, 1))(dy, w, "pallas")
    ddy_ref, dw_ref = jax.grad(loss, argnums=(0, 1))(dy, w, "reference")
    assert_allclose(ddy, ddy_ref, rtol=2e-4, atol=2e-4,
                    err_msg=f"{name} ddy")
    assert_allclose(dw, dw_ref, rtol=2e-4, atol=2e-4, err_msg=f"{name} dw")


CT_RAGGED_TILES = [
    # (B, O, K, S, P, Ci, Co, ci_t, co_t, u)
    (2, 5, 3, 2, 0, 5, 20, 2, 8, 1),
    (1, 5, 3, 2, 0, 5, 20, 2, 8, 3),
    (3, 4, 4, 2, 1, 7, 9, 4, 4, 16),
]


@pytest.mark.parametrize("B,O,K,S,P,Ci,Co,ci_t,co_t,u", CT_RAGGED_TILES)
def test_fused_ct_backward_ragged_tiles(rng, B, O, K, S, P, Ci, Co, ci_t,
                                        co_t, u):
    spec = ConvSpec.make(stride=S, padding=P, filter_shape=K)
    n = spec.input_size((O, O))[0]
    g = jnp.asarray(rng.normal(size=(B, n, n, Ci)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
    ddy, dw = tconv_backward_pallas(g, dy, w, stride=(S, S),
                                    padding=(P, P), cin_tile=ci_t,
                                    cout_tile=co_t, tap_unroll=u,
                                    interpret=True)
    be = resolve_backend("reference")
    assert_allclose(ddy, be.forward(g, w, spec), rtol=2e-4, atol=2e-4)
    assert_allclose(dw, be.filter_grad(g, dy, spec), rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# structural pins of the fusion
# ---------------------------------------------------------------------------

def test_backward_single_launch_both_outputs(rng):
    """jax.grad of a pallas-backend conv traces exactly ONE pallas_call,
    and that launch emits BOTH gradients (two output refs: the
    phase-major dx accumulator and the stationary tap-major dW block)."""
    B, N, K, S, Ci, Co = 2, 9, 3, 2, 3, 5
    x, w, dy = _case(rng, B, N, K, S, 0, 1, Ci, Co)
    loss = lambda x_, w_: jnp.vdot(ecoflow_conv(x_, w_, S, 0, "pallas"),
                                   dy)
    g = lambda x_, w_: jax.grad(loss, argnums=(0, 1))(x_, w_)
    assert count_pallas_calls(g, x, w) == 1
    jaxpr = jax.make_jaxpr(g)(x, w)
    pallas_eqns = [e for e in walk_eqns(jaxpr.jaxpr)
                   if e.primitive.name == "pallas_call"]
    out_shapes = [tuple(v.aval.shape) for v in pallas_eqns[0].outvars]
    assert len(out_shapes) == 2, out_shapes
    # (B, T, ho, wo, Cin) phase-major dx + (Kh*Kw, Cin, Cout) dW.
    assert out_shapes[0][0] == B and out_shapes[0][-1] == Ci, out_shapes
    assert out_shapes[1] == (K * K, Ci, Co), out_shapes


def test_backward_no_duplicated_dy_intermediates(rng):
    """The error map is fetched ONCE: exactly one dy-sized Cout-channel
    intermediate (the single padded dy) appears in the traced backward --
    the two-launch path's second dy staging (the filter-grad slab
    reshape) is gone."""
    B, N, K, S, Ci, Co = 2, 9, 3, 2, 3, 5
    x, w, dy = _case(rng, B, N, K, S, 0, 1, Ci, Co)
    fn = lambda x_, dy_, w_: ops.conv_backward(
        x_, dy_, w_, stride=(S, S), padding=(0, 0), n_out=(N, N))
    jaxpr = jax.make_jaxpr(fn)(x, dy, w)
    dy_sized = []
    for e in walk_eqns(jaxpr.jaxpr):
        if e.primitive.name in ("jit", "custom_jvp_call",
                                "custom_vjp_call_jaxpr"):
            continue   # call wrappers re-report their sub-jaxpr's output
        for v in e.outvars:
            shape = tuple(getattr(v.aval, "shape", ()))
            if len(shape) >= 4 and shape[-1] == Co \
                    and int(np.prod(shape)) >= dy.size:
                dy_sized.append((e.primitive.name, shape))
    assert len(dy_sized) == 1, dy_sized
    assert dy_sized[0][0] == "pad", dy_sized      # the one padded dy


def test_backward_grid_and_block_shapes(rng):
    """Grid (Cin_t, B, T/pu, Cout_t, TK/u) with the phase axis OUTSIDE
    the Cout axis; the dy block carries a Cout tile of the full padded
    frame (the shared fetch), the x block a Cin tile, and the dW block
    is stationary across (b, phase, co, tap): (T_w, ci_t, Cout_pad)."""
    B, N, K, S, Ci, Co, ci_t, co_t = 2, 9, 3, 2, 8, 20, 4, 8
    x, w, dy = _case(rng, B, N, K, S, 0, 1, Ci, Co)
    fn = lambda x_, dy_, w_: conv_backward_pallas(
        x_, dy_, w_, stride=(S, S), padding=(0, 0), n_out=(N, N),
        cin_tile=ci_t, cout_tile=co_t, tap_unroll=1, phase_unroll=1,
        interpret=True)
    grids = pallas_grids(fn, x, dy, w)
    assert len(grids) == 1
    T = min(S, K) ** 2
    TK = (-(-K // S)) ** 2
    n_ci, n_co = -(-Ci // ci_t), -(-Co // co_t)
    assert grids[0] == (n_ci, B, T, n_co, TK), grids[0]
    blocks = pallas_block_shapes(fn, x, dy, w)[0]
    dy_blk, w_blk, x_blk, dx_blk, dw_blk = blocks
    assert dy_blk[-1] == co_t, blocks             # dy: Cout tile
    assert x_blk[-1] == ci_t, blocks              # x: Cin tile
    assert dx_blk[-1] == ci_t, blocks             # dx: Cin tile
    # dW: stationary block spans ALL taps and full (padded) Cout width,
    # so the sequential co axis never interrupts its visit streak.
    assert dw_blk == (K * K, ci_t, n_co * co_t), blocks


def test_ct_backward_single_launch_both_outputs(rng):
    """The transposed conv's ENTIRE backward is one pallas_call emitting
    (ddy, dW) -- the generator layer's gradient no longer pays a
    separate forward-conv launch plus a filter-grad launch."""
    B, O, K, S, P, Ci, Co = 2, 5, 4, 2, 1, 4, 6
    spec = ConvSpec.make(stride=S, padding=P, filter_shape=K)
    n = spec.input_size((O, O))[0]
    dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
    g = jnp.asarray(rng.normal(size=(B, n, n, Ci)), jnp.float32)
    fn = lambda g_, dy_, w_: ops.tconv_backward(
        g_, dy_, w_, stride=(S, S), padding=(P, P))
    assert count_pallas_calls(fn, g, dy, w) == 1
    jaxpr = jax.make_jaxpr(fn)(g, dy, w)
    pallas_eqns = [e for e in walk_eqns(jaxpr.jaxpr)
                   if e.primitive.name == "pallas_call"]
    out_shapes = [tuple(v.aval.shape) for v in pallas_eqns[0].outvars]
    assert len(out_shapes) == 2, out_shapes
    assert out_shapes[0] == (B, O, O, Co), out_shapes
    assert out_shapes[1] == (K * K, Ci, Co), out_shapes


def test_grad_through_models_single_backward_launch(rng):
    """End to end through jax.grad of a two-conv model on the pallas
    backend: one fused backward launch PER LAYER (plus the dilation-1
    forward convs, which are XLA on the unfused path) -- zero call-site
    changes.  With the declarative relu epilogue (the model default) the
    forward also becomes one fused pallas launch per layer, so the whole
    train step is exactly two launches per layer."""
    from repro.models import cnn
    params = cnn.simple_cnn_init(jax.random.PRNGKey(0), in_ch=3,
                                 widths=(4, 6), n_classes=4)
    x = jnp.asarray(rng.normal(size=(2, 8, 8, 3)), jnp.float32)
    y = jnp.asarray([0, 1])
    loss = lambda p: cnn.cnn_loss(p, x, y, stride=2, backend="pallas",
                                  fuse_epilogue=False)
    g = lambda p: jax.grad(loss)(p)
    assert count_pallas_calls(g, params) == 2      # one per conv layer
    loss_ep = lambda p: cnn.cnn_loss(p, x, y, stride=2, backend="pallas")
    g_ep = lambda p: jax.grad(loss_ep)(p)
    # fwd + bwd fused launches per layer, relu tails in-kernel.
    assert count_pallas_calls(g_ep, params) == 4
