"""Pallas TPU kernel: fused zero-free dilated (atrous) forward convolution.

EcoFlow's dilated-forward dataflow: the atrous filter is applied at tap
spacing D without ever materializing its K_eff = D*(K-1)+1 effective
extent -- the (K_eff^2 - K^2) inserted filter zeros that a naive lowering
schedules as real MACs simply never exist.

TPU mapping (the EcoFlow -> MXU translation, see DESIGN.md Sec. 2.4): the
**dilation taps are the grid** -- ONE `pallas_call` with the useful-tap
index t = kx*Kw + ky as its innermost (sequential) axis.  Each grid step
realizes one per-tap multicast group inside the kernel: the once-padded
input block is VMEM-resident, the step reads its tap window at offset
(kx*D_h, ky*D_w) from the block ref, strided by the output stride
(`tap_gather.gather_tap`), and contracts
the gathered (Oh*Ow, Cin_t) slab with that tap's (Cin_t, Cout_t) weights
on the MXU.  Partial products accumulate into the fp32 output tile across
the sequential (Cin-tile, tap) steps -- the Pallas equivalent of the
paper's local psum register.

BlockSpec tiling: grid (B, Cout_t, Cin_t, T/u) with the tap steps
innermost (u taps unroll per step -- static offsets when a single step
remains); per step the kernel holds
  x block   (1, Hp, Wp, Ci_t)    -- padded once; index map depends only on
                                    (b, ci), so it is NOT re-fetched
                                    across the tap axis
  w block   (u, Ci_t, Co_t)      -- this step's taps' weights, Cin tile
  out block (1, Oh, Ow, Co_t)    -- fp32 accumulator across (ci, tap)
in VMEM.  The Cin axis is a second sequential-accumulation axis, so the
padded-input working set no longer spans full channel depth (the old
layout held (1, Hp, Wp, Cin) whole).  Tile extents are chosen per
geometry by `kernels/tiling.py` (exact channel counts when small --
no pad/slice -- MXU-aligned 128 tiles at depth); see DESIGN.md Sec. 2.6.
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


def _df_kernel(x_ref, w_ref, *refs, sh: int, sw: int, dh: int, dw: int,
               oh: int, ow: int, kw: int, u: int, n_t: int, n_ci: int,
               seq1: bool, ep=None):
    # refs = ([bias_ref,] out_ref): the bias input exists only when the
    # epilogue carries one, so the epilogue-free launch keeps the exact
    # legacy in_specs (and jaxpr pins).
    bias_ref = refs[0] if len(refs) == 2 else None
    out_ref = refs[-1]
    ci = pl.program_id(2)
    # With a single tap step, ts is a python int and every tap gather
    # below is a STATIC strided read of the resident block.
    ts = pl.program_id(3) if n_t > 1 else 0
    ci_t = x_ref.shape[-1]
    acc = None
    for j in range(u):
        kx, ky = split_index(ts, u, j, kw)
        tap = gather_tap(x_ref, (0,), kx, ky, sh=sh, sw=sw, dh=dh, dw=dw,
                         oh=oh, ow=ow)                 # (oh, ow, ci_t)
        lhs = tap.reshape(oh * ow, ci_t).astype(jnp.float32)
        rhs = w_ref[j].astype(jnp.float32)             # (ci_t, co_t)
        prod = jax.lax.dot(lhs, rhs, preferred_element_type=jnp.float32)
        acc = prod if acc is None else acc + prod
    acc = acc.reshape(oh, ow, out_ref.shape[-1])

    def _tail(vals):  # epilogue on the VMEM-resident block, pre-store
        return ep.apply(vals, None if bias_ref is None else bias_ref[0])

    if seq1:       # single sequential step: every visit initializes
        out_ref[0] = _tail(acc) if ep is not None else acc
        return
    first = (ci == 0) if n_t == 1 else ((ci == 0) & (pl.program_id(3) == 0))

    @pl.when(first)
    def _init():
        out_ref[0] = acc

    @pl.when(jnp.logical_not(first))
    def _acc():
        out_ref[0] += acc

    if ep is not None:
        # Last sequential visit of this output tile: apply the epilogue
        # to the finished accumulator before it leaves VMEM.
        last = (ci == n_ci - 1)
        if n_t > 1:
            last &= pl.program_id(3) == n_t - 1

        @pl.when(last)
        def _epilogue():
            out_ref[0] = _tail(out_ref[0])


@functools.partial(jax.jit, static_argnames=("stride", "padding", "dilation",
                                             "cin_tile", "cout_tile",
                                             "tap_unroll", "interpret",
                                             "epilogue"))
def dconv_forward_pallas(x: jax.Array, w: jax.Array, *, stride=(1, 1),
                         padding=(0, 0), dilation=(2, 2),
                         bias: jax.Array | None = None,
                         epilogue=None,
                         cin_tile: int | None = None,
                         cout_tile: int | None = None,
                         tap_unroll: int | None = None,
                         interpret: bool) -> jax.Array:
    """Zero-free dilated forward conv in a SINGLE `pallas_call`.

    x: (B, Nh, Nw, Cin) input.
    w: (Kh, Kw, Cin, Cout) undilated filter, applied at tap spacing D.
    Returns (B, Oh, Ow, Cout) with O = floor((N + 2P - K_eff)/S) + 1.
    Channel tiles default to the geometry-aware planner in
    `kernels/tiling.py`; pass them explicitly to pin a tiling.

    `epilogue` (an `Epilogue`, static) fuses act(scale * conv + bias)
    onto the resident output block before its HBM store; `bias` is the
    (Cout,) vector when the epilogue carries one.
    """
    sh, sw = _pair(stride)
    ph, pw = _pair(padding)
    dh, dw = _pair(dilation)
    B, Nh, Nw, Cin = x.shape
    Kh, Kw, _, Cout = w.shape
    spec = ConvSpec.make(stride=(sh, sw), padding=(ph, pw),
                         filter_shape=(Kh, Kw), dilation=(dh, dw))
    Oh, Ow = spec.out_size((Nh, Nw))
    if Oh < 1 or Ow < 1:   # ValueError, not assert: survives `python -O`
        raise ValueError(
            f"input {(Nh, Nw)} too small for effective filter "
            f"{spec.dilated_filter_shape} at padding {(ph, pw)}")
    if epilogue is not None and epilogue.is_identity:
        epilogue = None
    if epilogue is not None and epilogue.bias and bias is None:
        raise ValueError("epilogue.bias=True but no bias array was given")
    if None in (cin_tile, cout_tile, tap_unroll):
        plan = tiling.plan_tiles("forward", spec, x_shape=x.shape,
                                 dy_shape=(B, Oh, Ow, Cout),
                                 itemsize=x.dtype.itemsize,
                                 interpret=interpret, epilogue=epilogue)
        cin_tile = plan.cin_tile if cin_tile is None else cin_tile
        cout_tile = plan.cout_tile if cout_tile is None else cout_tile
        tap_unroll = plan.tap_unroll if tap_unroll is None else tap_unroll
    xp = jnp.pad(x, ((0, 0), (ph, ph), (pw, pw), (0, 0)))
    xp = pad_to_tap_windows(xp, stride=(sh, sw), dilation=(dh, dw),
                            k=(Kh, Kw), out_size=(Oh, Ow))
    hp, wp = xp.shape[1], xp.shape[2]
    T = Kh * Kw
    ci_t = min(cin_tile, Cin)
    co_t = min(cout_tile, Cout)
    n_ci, n_co = -(-Cin // ci_t), -(-Cout // co_t)
    w_taps = w.reshape(T, Cin, Cout)
    if Cin % ci_t:
        xp = jnp.pad(xp, ((0, 0),) * 3 + ((0, n_ci * ci_t - Cin),))
        w_taps = jnp.pad(w_taps,
                         ((0, 0), (0, n_ci * ci_t - Cin), (0, 0)))
    if Cout % co_t:
        w_taps = jnp.pad(w_taps,
                         ((0, 0), (0, 0), (0, n_co * co_t - Cout)))
    u = tiling.largest_divisor_leq(T, tap_unroll)
    n_t = T // u
    kern = functools.partial(_df_kernel, sh=sh, sw=sw, dh=dh, dw=dw,
                             oh=Oh, ow=Ow, kw=Kw, u=u, n_t=n_t,
                             n_ci=n_ci, seq1=(n_ci == 1 and n_t == 1),
                             ep=epilogue)
    in_specs = [
        pl.BlockSpec((1, hp, wp, ci_t),
                     lambda b, co, ci, t: (b, 0, 0, ci)),
        pl.BlockSpec((u, ci_t, co_t),
                     lambda b, co, ci, t: (t, ci, co)),
    ]
    ins = [xp, w_taps]
    if epilogue is not None and epilogue.bias:
        bp = bias.astype(jnp.float32).reshape(1, Cout)
        if Cout % co_t:
            bp = jnp.pad(bp, ((0, 0), (0, n_co * co_t - Cout)))
        in_specs.append(pl.BlockSpec((1, co_t),
                                     lambda b, co, ci, t: (0, co)))
        ins.append(bp)
    out = pl.pallas_call(
        kern,
        grid=(B, n_co, n_ci, n_t),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Oh, Ow, co_t),
                               lambda b, co, ci, t: (b, 0, 0, co)),
        out_shape=jax.ShapeDtypeStruct((B, Oh, Ow, n_co * co_t),
                                       jnp.float32),
        interpret=interpret,
        compiler_params=tiling.compiler_params(),
    )(*ins)
    if Cout % co_t:   # slice only when channel padding occurred
        out = out[..., :Cout]
    return out.astype(x.dtype)


def _autotune_runner(spec: ConvSpec, x_shape, dy_shape, epilogue=None):
    """Autotune hook: execute the real kernel at one candidate plan."""
    x = jnp.zeros(x_shape, jnp.float32)
    w = jnp.zeros(spec.filter_shape + (x_shape[-1], dy_shape[-1]),
                  jnp.float32)
    bias = (jnp.zeros((dy_shape[-1],), jnp.float32)
            if epilogue is not None and epilogue.bias else None)
    from repro.kernels.ops import interpret_mode
    interp = interpret_mode()

    def run(plan: tiling.TilePlan):
        return jax.block_until_ready(dconv_forward_pallas(
            x, w, stride=spec.stride, padding=spec.padding,
            dilation=spec.dilation, bias=bias, epilogue=epilogue,
            cin_tile=plan.cin_tile,
            cout_tile=plan.cout_tile, tap_unroll=plan.tap_unroll,
            interpret=interp))

    return run


tiling.register_autotune_runner("forward", _autotune_runner)
