"""Pallas TPU kernel: zero-free dilated-convolution filter gradient.

EcoFlow's filter-gradient dataflow (paper Sec. 4.2): one PE per filter
gradient element, each accumulating
sum_{b,i,j} x[b, iS+kx*D, jS+ky*D] * dy[b,i,j] locally, with the ifmap
delivered via per-tap multicast groups (D is the forward filter dilation,
1 for plain convs).

TPU mapping: the per-tap multicast group is realized INSIDE the kernel --
the padded input block is VMEM-resident and each grid step dynamic-slices
its tap window (kx*D, ky*D) out of it and subsamples by the stride, so the
K_h*K_w-replicated `x_taps` gather of the old formulation is never
materialized.  Each PE-column accumulation becomes one
(Cin x B*O*O) @ (B*O*O x Cout) MXU matmul -- and with tap unrolling, `u`
such matmuls run per grid step against the SAME resident blocks, with
static (compile-time) tap offsets.

BlockSpec tiling (geometry-aware, chosen by `kernels/tiling.py`):

    grid = (Cin_t, Cout_t, B, SP, T/u)     batch/spatial/tap SEQUENTIAL
    x block   (1, 1, rows_x, Wp, ci_t)     one spatial slab of the padded
                                           input; index map (b, sp, ci)
                                           -- resident across the tap axis
    dy block  (1, 1, sp, Ow, co_t)         this slab's error rows
    out block (T, ci_t, co_t)              fp32 accumulator: ALL taps of
                                           this channel tile, stationary
                                           across every (B, SP, tap) step

Batch and the spatial slabs are in-kernel fp32 accumulation axes: the
first (b=0, sp=0) step initializes each tap row of the out block, every
later step accumulates into it, and the block is flushed to HBM exactly
once per (ci, co) tile.  The (B, T, Cin, Cout) HBM partial slabs and the
host-side `out.sum(axis=0)` of the previous revision are gone.  The
PR 2 re-fetch lesson still holds: the padded-input block's index map
depends only on axes OUTSIDE the tap axis, so it is never re-fetched
while the taps of one slab stream; the out block's index map ignores all
three sequential axes, so its grid visits stay consecutive.

Spatial tiling: when the planner splits Oh into slabs, the wrapper
builds overlapping input slabs host-side (rows_x = (sp-1)*S + D*(K-1)+1
rows each -- the halo costs O(n_sp * K_eff) extra rows, not a full
Hp x Wp residency), so the x block never holds the full padded frame.
Tap unrolling: `u` taps per grid step as separate matmuls against the
resident blocks -- each tap slice is consumed before the next is
gathered, so unrolling never materializes a K^2-replicated tap stack.

See DESIGN.md Sec. 2.6 for the tiling policy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.spec import ConvSpec, _pair
from repro.kernels import tiling
from repro.kernels.tap_gather import (gather_tap, pad_to_tap_windows,
                                      split_index)


def _fg_kernel(x_ref, dy_ref, out_ref, *, sh: int, sw: int, dh: int,
               dw: int, sp: int, ow: int, kw: int, u: int, n_t: int,
               seq1: bool):
    # With a single tap step, ts is a python int and every tap gather
    # below is a STATIC strided read of the resident block.
    ts = pl.program_id(4) if n_t > 1 else 0
    ci_t = x_ref.shape[-1]
    co_t = dy_ref.shape[-1]
    rhs = dy_ref[0, 0].reshape(sp * ow, co_t).astype(jnp.float32)
    # seq1: B == n_sp == 1, so every visit to an out row is its first --
    # the init/accumulate predication compiles away entirely.
    first = None if seq1 else ((pl.program_id(2) == 0)
                               & (pl.program_id(3) == 0))

    def _store(t, prod, accumulate: bool):
        if isinstance(t, int):
            out_ref[t] = (out_ref[t] + prod) if accumulate else prod
        elif accumulate:
            out_ref[pl.ds(t, 1)] += prod[None]
        else:
            out_ref[pl.ds(t, 1)] = prod[None]

    for j in range(u):
        t = ts * u + j
        kx, ky = split_index(ts, u, j, kw)
        tap = gather_tap(x_ref, (0, 0), kx, ky, sh=sh, sw=sw, dh=dh, dw=dw,
                         oh=sp, ow=ow)                 # (sp, ow, ci_t)
        lhs = tap.reshape(sp * ow, ci_t).astype(jnp.float32)
        # One PE-column block per tap: (ci_t x sp*ow) @ (sp*ow x co_t).
        # Kept as per-tap matmuls (NOT one concatenated wide matmul): the
        # concat materializes a u-replicated tap stack and costs more
        # than it saves on both the interpret and Mosaic paths.
        prod = jax.lax.dot_general(
            lhs, rhs, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)        # (ci_t, co_t)
        if first is None:
            _store(t, prod, accumulate=False)
        else:
            @pl.when(first)
            def _init(t=t, prod=prod):
                _store(t, prod, accumulate=False)

            @pl.when(jnp.logical_not(first))
            def _acc(t=t, prod=prod):
                _store(t, prod, accumulate=True)


@functools.partial(jax.jit, static_argnames=("stride", "padding", "k",
                                             "dilation", "cin_tile",
                                             "cout_tile", "spatial_tile",
                                             "tap_unroll", "interpret"))
def dconv_filter_grad_pallas(x: jax.Array, dy: jax.Array, *, stride,
                             padding, k, dilation=(1, 1),
                             cin_tile: int | None = None,
                             cout_tile: int | None = None,
                             spatial_tile: int | None = None,
                             tap_unroll: int | None = None,
                             interpret: bool) -> jax.Array:
    """dW (Kh,Kw,Cin,Cout) for direct_conv(x, w, stride, padding, dilation).

    SINGLE `pallas_call`; the input is padded once and tap windows are
    sliced inside the kernel (no K^2 input replication on the host side).
    Batch and spatial slabs accumulate IN KERNEL into a stationary fp32
    out block -- no per-batch HBM partials, no host-side reduction.  Tile
    extents default to the geometry-aware planner in `kernels/tiling.py`;
    pass them explicitly to pin a tiling (tests do).
    """
    sh, sw = stride
    ph, pw = padding
    dh, dw = _pair(dilation)
    Kh, Kw = k
    B, Nh, Nw, Cin = x.shape
    _, Oh, Ow, Cout = dy.shape
    spec = ConvSpec.make(stride=(sh, sw), padding=(ph, pw),
                         filter_shape=(Kh, Kw), dilation=(dh, dw))
    T = Kh * Kw

    if None in (cin_tile, cout_tile, spatial_tile, tap_unroll):
        plan = tiling.plan_tiles("filter_grad", spec, x_shape=x.shape,
                                 dy_shape=dy.shape,
                                 itemsize=x.dtype.itemsize,
                                 interpret=interpret)
        cin_tile = plan.cin_tile if cin_tile is None else cin_tile
        cout_tile = plan.cout_tile if cout_tile is None else cout_tile
        spatial_tile = plan.spatial_tile if spatial_tile is None \
            else spatial_tile
        tap_unroll = plan.tap_unroll if tap_unroll is None else tap_unroll
    ci_t, co_t = min(cin_tile, Cin), min(cout_tile, Cout)
    sp = max(1, min(spatial_tile, Oh))
    u = tiling.largest_divisor_leq(T, tap_unroll)
    n_ci, n_co = -(-Cin // ci_t), -(-Cout // co_t)
    n_sp, n_t = -(-Oh // sp), T // u
    oh_pad = n_sp * sp

    xp = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    xp = pad_to_tap_windows(xp, stride=(sh, sw), dilation=(dh, dw),
                            k=(Kh, Kw), out_size=(oh_pad, Ow))
    rows_x = (sp - 1) * sh + dh * (Kh - 1) + 1
    if n_sp > 1:
        # Overlapping spatial slabs (halo = D*(K-1) + S-1 rows each): the
        # kernel's x block holds ONE slab, never the full padded frame.
        x_sl = jnp.stack([jax.lax.slice_in_dim(xp, s * sp * sh,
                                               s * sp * sh + rows_x, axis=1)
                          for s in range(n_sp)], axis=1)
    else:
        x_sl = xp[:, None]                 # (B, 1, Hp, Wp, Cin)
    wp = x_sl.shape[3]
    # Channel pad only when the tile does not divide the channel count
    # (the planner prefers exact tiles, making this a no-op on most nets).
    if Cin % ci_t:
        x_sl = jnp.pad(x_sl, ((0, 0),) * 4 + ((0, n_ci * ci_t - Cin),))
    dy_p = dy
    if Cout % co_t:
        dy_p = jnp.pad(dy_p, ((0, 0),) * 3 + ((0, n_co * co_t - Cout),))
    if oh_pad != Oh:    # zero error rows contribute nothing to dW
        dy_p = jnp.pad(dy_p, ((0, 0), (0, oh_pad - Oh), (0, 0), (0, 0)))
    dy_sl = dy_p.reshape(B, n_sp, sp, Ow, n_co * co_t)

    kern = functools.partial(_fg_kernel, sh=sh, sw=sw, dh=dh, dw=dw,
                             sp=sp, ow=Ow, kw=Kw, u=u, n_t=n_t,
                             seq1=(B == 1 and n_sp == 1))
    out = pl.pallas_call(
        kern,
        grid=(n_ci, n_co, B, n_sp, n_t),
        in_specs=[
            pl.BlockSpec((1, 1, rows_x, wp, ci_t),
                         lambda ci, co, b, s, t: (b, s, 0, 0, ci)),
            pl.BlockSpec((1, 1, sp, Ow, co_t),
                         lambda ci, co, b, s, t: (b, s, 0, 0, co)),
        ],
        out_specs=pl.BlockSpec((T, ci_t, co_t),
                               lambda ci, co, b, s, t: (0, ci, co)),
        out_shape=jax.ShapeDtypeStruct((T, n_ci * ci_t, n_co * co_t),
                                       jnp.float32),
        interpret=interpret,
        compiler_params=tiling.compiler_params(),
    )(x_sl, dy_sl)
    if Cin % ci_t or Cout % co_t:   # slice only when padding occurred
        out = out[:, :Cin, :Cout]
    return out.reshape(Kh, Kw, Cin, Cout).astype(x.dtype)


def _autotune_runner(spec: ConvSpec, x_shape, dy_shape):
    """Autotune hook: execute the real kernel at one candidate plan (fp32
    proxy operands; geometry, not values, determines the timing)."""
    x = jnp.zeros(x_shape, jnp.float32)
    dy = jnp.zeros(dy_shape, jnp.float32)
    from repro.kernels.ops import interpret_mode
    interp = interpret_mode()

    def run(plan: tiling.TilePlan):
        return jax.block_until_ready(dconv_filter_grad_pallas(
            x, dy, stride=spec.stride, padding=spec.padding,
            k=spec.filter_shape, dilation=spec.dilation,
            cin_tile=plan.cin_tile, cout_tile=plan.cout_tile,
            spatial_tile=plan.spatial_tile, tap_unroll=plan.tap_unroll,
            interpret=interp))

    return run


tiling.register_autotune_runner("filter_grad", _autotune_runner)
