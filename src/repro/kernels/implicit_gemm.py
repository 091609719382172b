"""Pallas TPU kernel: implicit-GEMM transposed / input-gradient conv.

The phase decomposition (kernels/tconv_phase.py) is EcoFlow's zero-free
answer to the strided transposed conv; this module is the strongest
in-repo baseline it races against -- the predicated implicit-GEMM
formulation of microsoft/AttentionEngine's `conv_transpose_example.py`
(SNIPPETS.md Snippet 1): ONE flat GEMM over

    (M = B * Fh * Fw) x (K = Kh * Kw * Cout)

where every (output site, tap) lane carries an in-bound predicate

    h = r - kx*Dh        in_bound = (h % Sh == 0) and 0 <= h // Sh < Oh

and out-of-bound lanes contribute zero.  No phase bookkeeping, no
per-phase sub-filter packing, no host-side residue interleave -- at the
cost of predicated (wasted) MXU lanes: the masked fraction is exactly
`ecoflow.predicated_mac_fraction(spec, (Oh, Ow))` = 1 - Oh*Ow/(Fh*Fw).

TPU realization of the predicate: Mosaic has no per-element gather, so
the `h % S == 0` mask is realized STRUCTURALLY -- the VMEM-resident dy
block is zero-interleaved in-register (a concat + reshape upsample; the
zeros exist only in VMEM, never in HBM) and padded by the tap reach
Dh*(Kh-1) per side, after which every tap's contribution is a STATIC
window of that frame feeding a plain MXU matmul:

    dx_full[r, s] += up[r + (Kh-1-kx)*Dh, s + (Kw-1-ky)*Dw] . W[kx,ky]^T

with `up` the padded upsampled frame (extent Fh + Dh*(Kh-1) per axis).
This is lane-for-lane the predicated flat GEMM: the zero lanes ARE the
failed predicates, multiplied instead of branched -- the exact trade the
strategy planner's waste term prices (DESIGN.md Sec. 2.10).  There is no
scatter and no `lhs/rhs_dilation` conv anywhere in this path (structural
pins in tests/test_implicit_gemm.py).

BlockSpec tiling: grid (B, Cin_t, Cout_t, T/u); per grid step the kernel
holds
  dy block  (1, Oh, Ow, Co_t)    -- the UNPADDED error tile (index map
                                    (b, co) only: resident across taps)
  w block   (u, Co_t, Ci_t)      -- this step's flat-tap weights
  out block (1, Fh, Fw, Ci_t)    -- fp32 accumulator across (co, tap)
in VMEM, plus the transient upsampled frame.  The epilogue slot is wired
like every other family: act(scale * . + bias) applied to the resident
accumulator on the LAST visit, so positions no tap reaches take
epilogue(0) = act(bias) with no host-side fill gather.  Host side does
only the padding crop (+ non-exact-fit tail fill), then casts back.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.spec import ConvSpec, _pair
from repro.kernels import tiling


def _upsample_pad(dyv: jax.Array, sh: int, sw: int, gh: int, gw: int
                  ) -> jax.Array:
    """Zero-interleave a (Oh, Ow, C) block by (sh, sw) and pad both sides
    by the tap reach (gh, gw).  This materializes the failed predicate
    lanes as VMEM zeros: row r of the result is dy[r' // sh] when
    r' = r - gh satisfies r' % sh == 0 and r' // sh < Oh, else zero --
    the `h_idx % S == 0` in-bound mask of the flat-GEMM formulation."""
    oh, ow, c = dyv.shape
    if sw > 1:
        z = jnp.zeros((oh, ow, sw - 1, c), dyv.dtype)
        dyv = jnp.concatenate([dyv[:, :, None, :], z], axis=2)
        dyv = dyv.reshape(oh, ow * sw, c)[:, :(ow - 1) * sw + 1]
    if sh > 1:
        w_up = dyv.shape[1]
        z = jnp.zeros((oh, sh - 1, w_up, c), dyv.dtype)
        dyv = jnp.concatenate([dyv[:, None], z], axis=1)
        dyv = dyv.reshape(oh * sh, w_up, c)[:(oh - 1) * sh + 1]
    return jnp.pad(dyv, ((gh, gh), (gw, gw), (0, 0)))


def _ig_kernel(dy_ref, w_ref, *refs, sh: int, sw: int, dh: int, dw: int,
               kh: int, kwf: int, fh: int, fw: int, u: int, n_k: int,
               seq1: bool, ep=None):
    """`u` flat taps per sequential grid step: upsample the resident dy
    tile in VMEM, slice each tap's (Fh, Fw) window (static offsets when a
    single tap step remains), one MXU matmul per tap against its
    (Co_t, Ci_t) weights, accumulate the fp32 out tile across the
    sequential (Cout-tile, tap-step) grid axes.

    refs = ([bias_ref,] out_ref); `ep` fuses act(scale * . + bias) onto
    the finished full-extent tile before its HBM store."""
    bias_ref = refs[0] if len(refs) == 2 else None
    out_ref = refs[-1]
    co = pl.program_id(2)
    k0 = pl.program_id(3) * u if n_k > 1 else 0
    gh, gw = dh * (kh - 1), dw * (kwf - 1)
    up = _upsample_pad(dy_ref[0], sh, sw, gh, gw)
    # seq1: single sequential (Cout-tile, tap) step -> unconditional
    # init, inline epilogue.
    first = None if seq1 else (
        (co == 0) if n_k == 1 else ((co == 0) & (pl.program_id(3) == 0)))
    last = None
    if ep is not None and not seq1:
        last = (co == pl.num_programs(2) - 1)
        if n_k > 1:
            last &= pl.program_id(3) == n_k - 1

    def _tail(vals):
        return ep.apply(vals, None if bias_ref is None else bias_ref[0])

    acc = None
    for j in range(u):
        k = k0 + j
        kx, ky = k // kwf, k % kwf
        start_h = (kh - 1 - kx) * dh
        start_w = (kwf - 1 - ky) * dw
        if isinstance(start_h, int) and isinstance(start_w, int):
            win = up[start_h:start_h + fh, start_w:start_w + fw]
        else:
            # Interpret mode only: Mosaic has no value-level dynamic_slice,
            # so the compiled planner unrolls every tap (static offsets).
            win = jax.lax.dynamic_slice(
                up, (start_h, start_w, 0), (fh, fw, up.shape[-1]))
        lhs = win.reshape(fh * fw, win.shape[-1]).astype(jnp.float32)
        rhs = w_ref[j].astype(jnp.float32)           # (co_t, ci_t)
        prod = jax.lax.dot(lhs, rhs, preferred_element_type=jnp.float32)
        acc = prod if acc is None else acc + prod
    acc = acc.reshape(fh, fw, out_ref.shape[-1])
    if first is None:
        out_ref[0] = _tail(acc) if ep is not None else acc
    else:
        @pl.when(first)
        def _init():
            out_ref[0] = acc

        @pl.when(jnp.logical_not(first))
        def _acc():
            out_ref[0] += acc

        if ep is not None:
            @pl.when(last)
            def _epilogue():
                out_ref[0] = _tail(out_ref[0])


@functools.partial(jax.jit, static_argnames=("stride", "padding", "n_out",
                                             "dilation", "cin_tile",
                                             "cout_tile", "tap_unroll",
                                             "interpret", "epilogue"))
def tconv_implicit_gemm_pallas(dy: jax.Array, w: jax.Array, *, stride,
                               padding=(0, 0), n_out=None, dilation=(1, 1),
                               bias: jax.Array | None = None,
                               epilogue=None,
                               cin_tile: int | None = None,
                               cout_tile: int | None = None,
                               tap_unroll: int | None = None,
                               interpret: bool) -> jax.Array:
    """Predicated implicit-GEMM transposed conv in a SINGLE `pallas_call`,
    any (S, D).

    dy: (B, Oh, Ow, Cout) error / generator input.
    w:  (Kh, Kw, Cin, Cout) forward filter.
    Returns (B, Nh, Nw, Cin) where (Nh, Nw) = n_out (default exact fit).
    Same contract as `tconv_fused_pallas` -- the two are interchangeable
    behind `plan_strategy` -- but no phase machinery: the stride predicate
    lives in the VMEM zero-interleave, every tap is a static window.

    `epilogue` (static `Epilogue`) fuses act(scale * . + bias) in-kernel;
    `bias` is the (Cin,) vector (the tconv OUTPUT channels).
    """
    sh, sw = _pair(stride)
    ph, pw = _pair(padding)
    dh, dw = _pair(dilation)
    B, Oh, Ow, Cout = dy.shape
    Kh, Kw, Cin, _ = w.shape
    spec = ConvSpec.make(stride=(sh, sw), padding=(ph, pw),
                         filter_shape=(Kh, Kw), dilation=(dh, dw))
    if n_out is None:
        n_out = spec.input_size((Oh, Ow))
    Nh, Nw = _pair(n_out)
    Fh, Fw = spec.full_size((Oh, Ow))    # S(O-1) + D(K-1) + 1 pre-slice
    T = Kh * Kw

    # Flat tap-major weights: slot kx*Kw + ky holds W[kx, ky]^T.  No flip
    # and no per-phase packing -- the tap's window offset (Kh-1-kx)*Dh
    # realizes the transposed orientation.
    w_flat = w.transpose(0, 1, 3, 2).reshape(T, Cout, Cin)

    if epilogue is not None and epilogue.is_identity:
        epilogue = None
    if epilogue is not None and epilogue.bias and bias is None:
        raise ValueError("epilogue.bias=True but no bias array was given")
    if None in (cin_tile, cout_tile, tap_unroll):
        plan = tiling.plan_tiles("input_grad", spec,
                                 x_shape=(B, Nh, Nw, Cin),
                                 dy_shape=dy.shape,
                                 itemsize=dy.dtype.itemsize,
                                 interpret=interpret, epilogue=epilogue)
        cin_tile = plan.cin_tile if cin_tile is None else cin_tile
        cout_tile = plan.cout_tile if cout_tile is None else cout_tile
        tap_unroll = plan.tap_unroll if tap_unroll is None else tap_unroll
    ci_t = min(cin_tile, Cin)
    co_t = min(cout_tile, Cout)
    n_ci, n_co = -(-Cin // ci_t), -(-Cout // co_t)
    dy_in = dy
    if Cout % co_t:
        dy_in = jnp.pad(dy, ((0, 0),) * 3 + ((0, n_co * co_t - Cout),))
        w_flat = jnp.pad(w_flat, ((0, 0),
                                  (0, n_co * co_t - Cout), (0, 0)))
    if Cin % ci_t:
        w_flat = jnp.pad(w_flat, ((0, 0),) * 2 +
                         ((0, n_ci * ci_t - Cin),))

    u = tiling.largest_divisor_leq(T, tap_unroll)
    n_k = T // u
    kern = functools.partial(_ig_kernel, sh=sh, sw=sw, dh=dh, dw=dw,
                             kh=Kh, kwf=Kw, fh=Fh, fw=Fw, u=u, n_k=n_k,
                             seq1=(n_co == 1 and n_k == 1), ep=epilogue)
    in_specs = [
        pl.BlockSpec((1, Oh, Ow, co_t), lambda b, ci, co, k: (b, 0, 0, co)),
        pl.BlockSpec((u, co_t, ci_t), lambda b, ci, co, k: (k, co, ci)),
    ]
    ins = [dy_in, w_flat]
    if epilogue is not None and epilogue.bias:
        bp = bias.astype(jnp.float32).reshape(1, Cin)
        if Cin % ci_t:
            bp = jnp.pad(bp, ((0, 0), (0, n_ci * ci_t - Cin)))
        in_specs.append(pl.BlockSpec((1, ci_t),
                                     lambda b, ci, co, k: (0, ci)))
        ins.append(bp)
    out = pl.pallas_call(
        kern,
        grid=(B, n_ci, n_co, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Fh, Fw, ci_t),
                               lambda b, ci, co, k: (b, 0, 0, ci)),
        out_shape=jax.ShapeDtypeStruct((B, Fh, Fw, n_ci * ci_t),
                                       jnp.float32),
        interpret=interpret,
        compiler_params=tiling.compiler_params(),
    )(*ins)

    if Cin % ci_t:   # slice only when channel padding occurred
        out = out[..., :Cin]
    # Non-exact-fit tails (forward ignored input rows/cols) lie beyond
    # the Fh x Fw extent: no tap reaches them, so under an epilogue they
    # take epilogue(0) = act(bias) -- the same fill the phase path's
    # assembly supplies (nonzero only when a bias rides along).
    eh, ew = max(0, ph + Nh - Fh), max(0, pw + Nw - Fw)
    if eh or ew:
        if epilogue is not None and epilogue.bias:
            fv = epilogue.apply(jnp.zeros((Cin,), jnp.float32), bias)
            fv = fv.astype(out.dtype)
            if eh:
                out = jnp.concatenate(
                    [out, jnp.broadcast_to(fv, (B, eh, out.shape[2], Cin))],
                    axis=1)
            if ew:
                out = jnp.concatenate(
                    [out, jnp.broadcast_to(fv, (B, out.shape[1], ew, Cin))],
                    axis=2)
        else:
            out = jnp.pad(out, ((0, 0), (0, eh), (0, ew), (0, 0)))
    return out[:, ph:ph + Nh, pw:pw + Nw, :].astype(dy.dtype)


def _autotune_runner(spec: ConvSpec, x_shape, dy_shape, epilogue=None):
    """Autotune hook: execute the real kernel at one candidate plan."""
    dy = jnp.zeros(dy_shape, jnp.float32)
    w = jnp.zeros(spec.filter_shape + (x_shape[-1], dy_shape[-1]),
                  jnp.float32)
    bias = (jnp.zeros((x_shape[-1],), jnp.float32)
            if epilogue is not None and epilogue.bias else None)
    n_out = (x_shape[1], x_shape[2])
    from repro.kernels.ops import interpret_mode
    interp = interpret_mode()

    def run(plan: tiling.TilePlan):
        return jax.block_until_ready(tconv_implicit_gemm_pallas(
            dy, w, stride=spec.stride, padding=spec.padding, n_out=n_out,
            dilation=spec.dilation, bias=bias, epilogue=epilogue,
            cin_tile=plan.cin_tile, cout_tile=plan.cout_tile,
            tap_unroll=plan.tap_unroll, interpret=interp))

    return run


tiling.register_autotune_runner("input_grad", _autotune_runner,
                                strategy="implicit_gemm")
