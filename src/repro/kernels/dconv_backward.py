"""Pallas TPU kernels: fused dual-gradient conv backward -- BOTH
gradients of a convolution from ONE `pallas_call`.

A training step runs, per conv layer, the two backward dataflows the
paper accelerates -- the transposed conv (input gradient) and the
dilated conv (filter gradient) -- over the SAME error map.  Launching
them as two independent `pallas_call`s (PR 1-4) re-fetches `dy` from HBM
twice and pays two kernel dispatches; per the bench-host note, the
launch/step count dominates interpret-mode Pallas wall clock, so the
pair is the highest-leverage fusion target (HUGE^2 makes the same
observation for GAN training: efficiency comes from restructuring the
backward *pair*, not either kernel alone).

Two fusions live here, one per VJP in `core/conv.py`:

`conv_backward_pallas(x, dy, w)` -> (dx, dW)   [direct-conv VJP]
    The shared operand is `dy`.  One launch with TWO output refs:
      * dx via the unified (phase, tap) decomposition of
        `kernels/tconv_phase.py` -- each step windows the VMEM-resident
        padded dy block at its tap offset;
      * dW via the per-tap gather of `kernels/dconv_filtergrad.py` --
        the *unpadded* dy window is a STATIC slice of the SAME resident
        padded dy block, so the error map is fetched once and feeds both
        accumulations.
    Every packed (phase, slot) pair of the input-grad decomposition maps
    bijectively onto a filter tap kx = a + (KP-1-uf)*period (padding
    slots map past the filter extent and are skipped/masked), so the
    single (phase, tap) enumeration drives both gradients.

    grid = (Cin_t, B, T/pu, Cout_t, TK/u)      T = phases, TK = taps
      dy block  (1, hp, wp, Co_t)   index (b, co): the ONE dy fetch,
                                    resident across the tap axis
      w block   (pu, u, Co_t, Ci_t) packed rotated sub-filters
      x block   (1, Hp, Wp, Ci_t)   index (b, ci): resident across
                                    (phase, cout, tap)
      dx block  (1, pu, ho, wo, Ci_t) fp32, accumulates over (co, tap)
                                    -- a single CONSECUTIVE visit streak
                                    per (ci, b, phase), as in tconv
      dW block  (T_w, Ci_t, Cout_pad) fp32, index (ci): stationary
                                    across (b, phase, co, tap) -- spans
                                    full (padded) Cout so its streak is
                                    never interrupted by the co axis
    The phase axis sits OUTSIDE the Cout axis (unlike tconv) because the
    dx accumulator's visits must stay consecutive while the dW block
    stays stationary; with the common n_co == 1 plan the dy block is
    fetched once per (ci, b) and resident across everything else.

`tconv_backward_pallas(g, dy, w)` -> (ddy, dW)   [transposed-conv VJP]
    The generator-layer backward: z = tconv(dy, w), cotangent g.  Its
    pair is (conv(g, w), filter_grad(g, dy)) -- the shared operand is
    `g`, which sits in the INPUT role of both.  Each step's tap gather
    of the resident g block feeds TWO matmuls: against the tap's weights
    (-> ddy) and against the dy window (-> dW) -- the fusion shares the
    gather itself, not just the block fetch.

    grid = (B, Cin_t, Cout_t, T/u)
      g block   (1, Hp, Wp, Ci_t)   index (b, ci): the ONE g fetch
      w block   (u, Ci_t, Co_t)     this step's taps' weights
      dy block  (1, Oh, Ow, Co_t)   index (b, co)
      ddy block (1, Oh, Ow, Cout_pad) fp32, index (b): spans full Cout
                                    (per-co column writes via pl.ds) so
                                    its streak covers the whole b slice
      dW block  (T_w, Cin_pad, Cout_pad) fp32, constant index: a single
                                    streak over the entire grid; each
                                    (tap, ci, co) cell is visited once
                                    per batch step (init at b == 0)

Tile extents come from `kernels/tiling.py` ("backward"/"ct_backward"
ops) whose working-set model accounts for the JOINT residency: shared
operand block + both fp32 accumulators.  See DESIGN.md Sec. 2.7.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.spec import ConvSpec, _pair
from repro.kernels import tiling
from repro.kernels.tap_gather import (gather_tap, pad_to_tap_windows,
                                      read_window, split_index)
from repro.kernels.tconv_phase import (assemble_phase_major,
                                       pack_phase_filters)


# ---------------------------------------------------------------------------
# direct-conv VJP: (dx, dW) from one dy residency
# ---------------------------------------------------------------------------

def _clamp(v, hi: int):
    return min(v, hi) if isinstance(v, int) else jnp.minimum(v, hi)


def _bwd_kernel(dy_ref, w_ref, x_ref, *refs, tpw: int, kp: int,
                kq: int, kh: int, kwf: int, per_h: int, per_w: int, sh: int,
                sw: int, dil_h: int, dil_w: int, step_h: int, step_w: int,
                pad_h: int, pad_w: int, ho: int, wo: int, oh: int, ow: int,
                pu: int, n_t: int, u: int, n_k: int, n_b: int, n_ci: int,
                n_co: int, co_t: int, ep=None, has_y: bool = False,
                has_db: bool = False):
    # refs = ([y_ref,] dx_ref, dw_ref [, db_ref]): the forward-output
    # residual input and the bias-gradient output exist only when the
    # epilogue needs them, so the epilogue-free launch keeps the exact
    # legacy spec lists (and jaxpr pins).
    y_ref = refs[0] if has_y else None
    dx_ref, dw_ref = refs[1 if has_y else 0], refs[2 if has_y else 1]
    db_ref = refs[-1] if has_db else None
    b = pl.program_id(1)
    ts = pl.program_id(2) if n_t > 1 else 0
    co = pl.program_id(3)
    ks = pl.program_id(4) if n_k > 1 else 0
    # Activation-gradient masking IN-VMEM on the resident cotangent block
    # (DESIGN.md Sec. 2.8): dym = dy * act'(y) is the masked (unscaled)
    # cotangent feeding the bias gradient; dx/dW additionally carry the
    # epilogue's scalar scale.  Padded positions stay zero (dy pad is 0).
    # Every window is read from the resident dy (and y) block refs, so
    # the mask is applied per window.
    scale = None if ep is None else ep.scale

    def cot(h0, w0, nh, nw):
        """Masked, unscaled cotangent window of the padded dy block."""
        win = read_window(dy_ref, (0,), h0, w0, oh=nh, ow=nw)
        if y_ref is None:
            return win
        y_win = read_window(y_ref, (0,), h0, w0, oh=nh, ow=nw)
        return win * ep.grad_factor(y_win).astype(win.dtype)

    # The shared residency: the filter-grad side's UNPADDED error window
    # is a read of the same VMEM-resident padded dy block the input-grad
    # windows come from -- dy is fetched exactly once.
    co_w = dy_ref.shape[-1]
    dym_fg = cot(pad_h, pad_w, oh, ow).reshape(oh * ow, co_w).astype(
        jnp.float32)
    rhs_fg = dym_fg if scale is None else dym_fg * scale
    if db_ref is not None:
        # Bias gradient: channel-sum of the masked cotangent, accumulated
        # in-kernel as the launch's third output.  One contribution per
        # (batch, cout-tile) -- taken at the first (ci, phase, tap) step.
        dbc = dym_fg.sum(axis=0)                          # (co_t,)
        db_cols = slice(None) if n_co == 1 else pl.ds(co * co_t, co_t)
        take = []
        if n_ci > 1:
            take.append(pl.program_id(0) == 0)
        if n_t > 1:
            take.append(pl.program_id(2) == 0)
        if n_k > 1:
            take.append(pl.program_id(4) == 0)
        if n_b == 1:
            if take:
                @pl.when(functools.reduce(jnp.logical_and, take))
                def _db_set():
                    db_ref[0, db_cols] = dbc
            else:
                db_ref[0, db_cols] = dbc
        else:
            @pl.when(functools.reduce(jnp.logical_and, take + [b == 0]))
            def _db_init():
                db_ref[0, db_cols] = dbc

            @pl.when(functools.reduce(jnp.logical_and, take + [b > 0]))
            def _db_acc():
                db_ref[0, db_cols] += dbc
    dx_first = None if (n_co == 1 and n_k == 1) else (
        (co == 0) if n_k == 1 else ((co == 0) & (pl.program_id(4) == 0)))
    # Traced (phase, slot) indices (multiple phase/tap grid steps) cannot
    # skip padding slots at trace time: zero the stationary dW block at
    # the first step of its streak and always accumulate masked products.
    traced = n_t > 1 or n_k > 1
    if traced:
        conds = []
        if n_b > 1:
            conds.append(b == 0)
        if n_co > 1:
            conds.append(co == 0)
        if n_t > 1:
            conds.append(pl.program_id(2) == 0)
        if n_k > 1:
            conds.append(pl.program_id(4) == 0)
        zero = functools.reduce(jnp.logical_and, conds)

        @pl.when(zero)
        def _zero_dw():
            dw_ref[...] = jnp.zeros(dw_ref.shape, dw_ref.dtype)

    cols = slice(None) if n_co == 1 else pl.ds(co * co_t, co_t)
    for p in range(pu):
        a, bb = split_index(ts, pu, p, tpw)
        acc = None
        for j in range(u):
            uf, vf = split_index(ks, u, j, kq)
            # The shared (phase, slot) -> filter-tap enumeration.
            # Flipped-slot mapping (see pack_phase_filters): slot uf of
            # phase a holds tap kx = a + (KP-1-uf)*period; padding slots
            # of ragged phases land past the filter extent and carry
            # all-zero packed weights.
            kx = a + (kp - 1 - uf) * per_h
            ky = bb + (kq - 1 - vf) * per_w
            if not traced and (kx >= kh or ky >= kwf):
                # Padding slot, statically known: its dx matmul is a
                # multiply-by-zero and its dW product must not land --
                # skip BOTH.  (The standalone tconv kernel spends a zero
                # matmul here; the fused kernel's dW-side validity test
                # makes the deadness explicit for free.)  Safe because
                # `not traced` implies full (phase, tap) unroll, so every
                # phase sees its >= 1 valid slot within this step.
                continue
            # -- dx: this (phase, tap)'s window of the padded dy block --
            start_h = pad_h - (a * dil_h) // sh - (kp - 1 - uf) * step_h
            start_w = pad_w - (bb * dil_w) // sw - (kq - 1 - vf) * step_w
            win = cot(start_h, start_w, ho, wo)
            if scale is not None:
                win = win * scale
            lhs = win.reshape(ho * wo, co_w).astype(jnp.float32)
            rhs = w_ref[p, j].astype(jnp.float32)        # (co_t, ci_t)
            prod = jax.lax.dot(lhs, rhs,
                               preferred_element_type=jnp.float32)
            acc = prod if acc is None else acc + prod
            # -- dW: the same slot's filter tap, gathered from x --
            # (a padding slot on a traced grid reads the last real tap's
            # window so the read stays in the block; its product is
            # masked out below)
            tap = gather_tap(x_ref, (0,), _clamp(kx, kh - 1),
                             _clamp(ky, kwf - 1), sh=sh, sw=sw, dh=dil_h,
                             dw=dil_w, oh=oh, ow=ow)
            lhs_w = tap.reshape(oh * ow,
                                x_ref.shape[-1]).astype(jnp.float32)
            pw = jax.lax.dot_general(
                lhs_w, rhs_fg, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)      # (ci_t, co_t)
            if not traced:
                tf = kx * kwf + ky
                if n_b == 1:
                    dw_ref[tf, :, cols] = pw
                else:
                    @pl.when(b == 0)
                    def _init(tf=tf, pw=pw):
                        dw_ref[tf, :, cols] = pw

                    @pl.when(b > 0)
                    def _acc(tf=tf, pw=pw):
                        dw_ref[tf, :, cols] += pw
            else:
                valid = (kx < kh) & (ky < kwf)
                pw = jnp.where(valid, pw, 0.0)
                tf = jnp.where(valid, kx * kwf + ky, 0)
                dw_ref[pl.ds(tf, 1), :, cols] += pw[None]
        acc = acc.reshape(ho, wo, dx_ref.shape[-1])
        if dx_first is None:
            dx_ref[0, p] = acc
        else:
            @pl.when(dx_first)
            def _dx_init(p=p, acc=acc):
                dx_ref[0, p] = acc

            @pl.when(jnp.logical_not(dx_first))
            def _dx_acc(p=p, acc=acc):
                dx_ref[0, p] += acc


@functools.partial(jax.jit, static_argnames=("stride", "padding", "n_out",
                                             "dilation", "cin_tile",
                                             "cout_tile", "tap_unroll",
                                             "phase_unroll", "interpret",
                                             "epilogue"))
def conv_backward_pallas(x: jax.Array, dy: jax.Array, w: jax.Array, *,
                         stride, padding=(0, 0), n_out=None,
                         dilation=(1, 1), y: jax.Array | None = None,
                         epilogue=None,
                         cin_tile: int | None = None,
                         cout_tile: int | None = None,
                         tap_unroll: int | None = None,
                         phase_unroll: int | None = None,
                         interpret: bool):
    """(dx, dW) of direct_conv(x, w, stride, padding, dilation) w.r.t.
    cotangent dy, in a SINGLE `pallas_call` with two output refs.

    x:  (B, Nh, Nw, Cin) forward input (residual).
    dy: (B, Oh, Ow, Cout) error map -- fetched ONCE, shared by both
        gradient accumulations.
    w:  (Kh, Kw, Cin, Cout) forward filter.
    Returns (dx (B, Nh, Nw, Cin) as dy.dtype upcast-safe,
             dW (Kh, Kw, Cin, Cout) as x.dtype).
    Bit-identical (up to fp accumulation order) to
    (tconv_fused_pallas(dy, w), dconv_filter_grad_pallas(x, dy)).

    With `epilogue` (static `Epilogue`) this is the VJP of the
    epilogue-fused forward: `y` is the forward OUTPUT residual, the
    activation-gradient mask act'(y) is applied in-VMEM to the resident
    dy block before both matmuls, and when the epilogue carries a bias
    the bias gradient is accumulated in-kernel as a THIRD output --
    the return becomes (dx, dW, db|None).
    """
    sh, sw = _pair(stride)
    ph, pw_ = _pair(padding)
    dil_h, dil_w = _pair(dilation)
    B, Nh_x, Nw_x, Cin = x.shape
    _, Oh, Ow, Cout = dy.shape
    Kh, Kw, _, _ = w.shape
    spec = ConvSpec.make(stride=(sh, sw), padding=(ph, pw_),
                         filter_shape=(Kh, Kw), dilation=(dil_h, dil_w))
    if n_out is None:
        n_out = (Nh_x, Nw_x)
    Nh, Nw = _pair(n_out)
    if spec.out_size((Nh_x, Nw_x)) != (Oh, Ow):
        raise ValueError(
            f"dy spatial {dy.shape[1:3]} inconsistent with x spatial "
            f"{x.shape[1:3]} for stride={spec.stride}, "
            f"padding={spec.padding}, filter={spec.filter_shape}, "
            f"dilation={spec.dilation}: forward yields "
            f"{spec.out_size((Nh_x, Nw_x))}")
    Fh, Fw = spec.full_size((Oh, Ow))
    step_h, step_w = spec.tap_phase_step
    TPh, TPw = spec.n_tap_phases
    KP, KQ = spec.taps_per_phase
    T, TK = TPh * TPw, KP * KQ
    T_w = Kh * Kw

    w_packed = pack_phase_filters(w, (sh, sw), (dil_h, dil_w))
    w_flat = w_packed.reshape(T, TK, Cout, Cin)

    pad_h = spec.tap_phase_base(TPh - 1, 0) + (KP - 1) * step_h
    pad_w = spec.tap_phase_base(TPw - 1, 1) + (KQ - 1) * step_w
    ho, wo = -(-Fh // sh), -(-Fw // sw)
    dy_pad = jnp.pad(dy, ((0, 0), (pad_h, ho - Oh), (pad_w, wo - Ow),
                          (0, 0)))
    hp, wp = dy_pad.shape[1], dy_pad.shape[2]

    xp = jnp.pad(x, ((0, 0), (ph, ph), (pw_, pw_), (0, 0)))
    xp = pad_to_tap_windows(xp, stride=(sh, sw), dilation=(dil_h, dil_w),
                            k=(Kh, Kw), out_size=(Oh, Ow))
    xh, xw = xp.shape[1], xp.shape[2]

    if epilogue is not None and epilogue.is_identity:
        epilogue = None
    has_y = epilogue is not None and epilogue.needs_y
    has_db = epilogue is not None and epilogue.bias
    if has_y and y is None:
        raise ValueError("epilogue has an activation but no forward "
                         "output residual y was given")
    if None in (cin_tile, cout_tile, tap_unroll, phase_unroll):
        plan = tiling.plan_tiles("backward", spec, x_shape=x.shape,
                                 dy_shape=dy.shape,
                                 itemsize=dy.dtype.itemsize,
                                 interpret=interpret, epilogue=epilogue)
        cin_tile = plan.cin_tile if cin_tile is None else cin_tile
        cout_tile = plan.cout_tile if cout_tile is None else cout_tile
        tap_unroll = plan.tap_unroll if tap_unroll is None else tap_unroll
        phase_unroll = plan.phase_unroll if phase_unroll is None \
            else phase_unroll
    ci_t = min(cin_tile, Cin)
    co_t = min(cout_tile, Cout)
    n_ci, n_co = -(-Cin // ci_t), -(-Cout // co_t)
    if Cout % co_t:
        dy_pad = jnp.pad(dy_pad, ((0, 0),) * 3 + ((0, n_co * co_t - Cout),))
        w_flat = jnp.pad(w_flat, ((0, 0),) * 2 +
                         ((0, n_co * co_t - Cout), (0, 0)))
    if Cin % ci_t:
        w_flat = jnp.pad(w_flat, ((0, 0),) * 3 + ((0, n_ci * ci_t - Cin),))
        xp = jnp.pad(xp, ((0, 0),) * 3 + ((0, n_ci * ci_t - Cin),))
    co_pad = n_co * co_t

    u = tiling.largest_divisor_leq(TK, tap_unroll)
    pu = tiling.largest_divisor_leq(T, phase_unroll)
    n_k, n_t = TK // u, T // pu
    per_h, per_w = spec.tap_phase_period
    kern = functools.partial(
        _bwd_kernel, tpw=TPw, kp=KP, kq=KQ, kh=Kh, kwf=Kw, per_h=per_h,
        per_w=per_w, sh=sh, sw=sw, dil_h=dil_h, dil_w=dil_w, step_h=step_h,
        step_w=step_w, pad_h=pad_h, pad_w=pad_w, ho=ho, wo=wo, oh=Oh,
        ow=Ow, pu=pu, n_t=n_t, u=u, n_k=n_k, n_b=B, n_ci=n_ci, n_co=n_co,
        co_t=co_t, ep=epilogue, has_y=has_y, has_db=has_db)
    in_specs = [
        pl.BlockSpec((1, hp, wp, co_t),
                     lambda ci, b, t, co, k: (b, 0, 0, co)),
        pl.BlockSpec((pu, u, co_t, ci_t),
                     lambda ci, b, t, co, k: (t, k, co, ci)),
        pl.BlockSpec((1, xh, xw, ci_t),
                     lambda ci, b, t, co, k: (b, 0, 0, ci)),
    ]
    ins = [dy_pad, w_flat, xp]
    if has_y:
        # y rides next to dy with the identical padding/blocking so the
        # mask multiply is pure resident-block elementwise work.
        yp = jnp.pad(y, ((0, 0), (pad_h, ho - Oh), (pad_w, wo - Ow),
                         (0, 0)))
        if Cout % co_t:
            yp = jnp.pad(yp, ((0, 0),) * 3 + ((0, co_pad - Cout),))
        in_specs.append(pl.BlockSpec((1, hp, wp, co_t),
                                     lambda ci, b, t, co, k: (b, 0, 0, co)))
        ins.append(yp)
    out_specs = [
        pl.BlockSpec((1, pu, ho, wo, ci_t),
                     lambda ci, b, t, co, k: (b, t, 0, 0, ci)),
        pl.BlockSpec((T_w, ci_t, co_pad),
                     lambda ci, b, t, co, k: (0, ci, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, T, ho, wo, n_ci * ci_t), jnp.float32),
        jax.ShapeDtypeStruct((T_w, n_ci * ci_t, co_pad), jnp.float32),
    ]
    if has_db:
        out_specs.append(pl.BlockSpec((1, co_pad),
                                      lambda ci, b, t, co, k: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((1, co_pad), jnp.float32))
    outs = pl.pallas_call(
        kern,
        grid=(n_ci, B, n_t, n_co, n_k),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=tiling.compiler_params(),
    )(*ins)
    dx_pm, dw_flat = outs[0], outs[1]

    # dW: slice the channel pads, restore the (Kh, Kw) tap layout.
    if Cin % ci_t or Cout % co_t:
        dw_flat = dw_flat[:, :Cin, :Cout]
    dw = dw_flat.reshape(Kh, Kw, Cin, Cout).astype(x.dtype)

    # dx: phase-major -> strided interleave, shared with tconv.
    out = dx_pm
    if Cin % ci_t:
        out = out[..., :Cin]
    dx = assemble_phase_major(out, spec, n_out=(Nh, Nw),
                              full_size=(Fh, Fw)).astype(dy.dtype)
    if epilogue is None:
        return dx, dw
    db = outs[2][0, :Cout].astype(dy.dtype) if has_db else None
    return dx, dw, db


# ---------------------------------------------------------------------------
# transposed-conv VJP: (ddy, dW) from one g residency
# ---------------------------------------------------------------------------

def _ct_bwd_kernel(g_ref, w_ref, dy_ref, *refs, sh: int,
                   sw: int, dil_h: int, dil_w: int, oh: int, ow: int,
                   kwf: int, u: int, n_t: int, n_b: int, n_ci: int,
                   n_co: int, ci_t: int, co_t: int, ep=None,
                   has_z: bool = False, has_db: bool = False):
    # refs = ([z_ref,] ddy_ref, dw_ref [, db_ref]); z is the fused
    # transposed conv's own forward output, masking its cotangent g.
    z_ref = refs[0] if has_z else None
    ddy_ref, dw_ref = refs[1 if has_z else 0], refs[2 if has_z else 1]
    db_ref = refs[-1] if has_db else None
    b, ci, co = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    ts = pl.program_id(3) if n_t > 1 else 0
    # In-VMEM activation-gradient mask on the resident cotangent block:
    # every tap gather below reads g (and z) from the resident blocks and
    # masks the window, so both matmuls (ddy and dW) see the epilogue's
    # pullback without an extra HBM pass.
    scale = None if ep is None else ep.scale

    def g_tap(kx, ky):
        tap = gather_tap(g_ref, (0,), kx, ky, sh=sh, sw=sw, dh=dil_h,
                         dw=dil_w, oh=oh, ow=ow)         # (oh, ow, ci_t)
        if z_ref is not None:
            z_tap = gather_tap(z_ref, (0,), kx, ky, sh=sh, sw=sw, dh=dil_h,
                               dw=dil_w, oh=oh, ow=ow)
            tap = tap * ep.grad_factor(z_tap).astype(tap.dtype)
        return tap if scale is None else tap * scale

    rhs_fg = dy_ref[0].reshape(oh * ow, co_t).astype(jnp.float32)
    ci_cols = slice(None) if n_ci == 1 else pl.ds(ci * ci_t, ci_t)
    co_cols = slice(None) if n_co == 1 else pl.ds(co * co_t, co_t)
    if db_ref is not None:
        # Bias gradient over the tconv's OUTPUT channels (Cin): sum of
        # the masked (unscaled) cotangent, one contribution per
        # (batch, cin-tile) at the first (cout, tap) step.
        gm = g_ref[0]
        if z_ref is not None:
            gm = gm * ep.grad_factor(z_ref[0]).astype(gm.dtype)
        dbc = gm.astype(jnp.float32).sum(axis=(0, 1))       # (ci_t,)
        take = []
        if n_co > 1:
            take.append(co == 0)
        if n_t > 1:
            take.append(pl.program_id(3) == 0)
        if n_b == 1:
            if take:
                @pl.when(functools.reduce(jnp.logical_and, take))
                def _db_set():
                    db_ref[0, ci_cols] = dbc
            else:
                db_ref[0, ci_cols] = dbc
        else:
            @pl.when(functools.reduce(jnp.logical_and, take + [b == 0]))
            def _db_init():
                db_ref[0, ci_cols] = dbc

            @pl.when(functools.reduce(jnp.logical_and, take + [b > 0]))
            def _db_acc():
                db_ref[0, ci_cols] += dbc
    acc_f = None
    for j in range(u):
        t = ts * u + j
        kx, ky = split_index(ts, u, j, kwf)
        # ONE tap gather of the resident g block feeds BOTH matmuls.
        tap = g_tap(kx, ky)
        lhs = tap.reshape(oh * ow, ci_t).astype(jnp.float32)
        wt = w_ref[j].astype(jnp.float32)                # (ci_t, co_t)
        prod_f = jax.lax.dot(lhs, wt, preferred_element_type=jnp.float32)
        acc_f = prod_f if acc_f is None else acc_f + prod_f
        pw = jax.lax.dot_general(lhs, rhs_fg, (((0,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        # dW[t, ci tile, co tile]: visited once per batch step.
        ti = t if isinstance(t, int) else pl.ds(t, 1)
        pv = pw if isinstance(t, int) else pw[None]
        if n_b == 1:
            dw_ref[ti, ci_cols, co_cols] = pv
        else:
            @pl.when(b == 0)
            def _dw_init(ti=ti, pv=pv):
                dw_ref[ti, ci_cols, co_cols] = pv

            @pl.when(b > 0)
            def _dw_acc(ti=ti, pv=pv):
                dw_ref[ti, ci_cols, co_cols] += pv
    acc_f = acc_f.reshape(oh, ow, co_t)
    if n_ci == 1 and n_t == 1:
        ddy_ref[0, :, :, co_cols] = acc_f
    else:
        first = (ci == 0) if n_t == 1 else ((ci == 0)
                                            & (pl.program_id(3) == 0))

        @pl.when(first)
        def _ddy_init():
            ddy_ref[0, :, :, co_cols] = acc_f

        @pl.when(jnp.logical_not(first))
        def _ddy_acc():
            ddy_ref[0, :, :, co_cols] += acc_f


@functools.partial(jax.jit, static_argnames=("stride", "padding",
                                             "dilation", "cin_tile",
                                             "cout_tile", "tap_unroll",
                                             "interpret", "epilogue"))
def tconv_backward_pallas(g: jax.Array, dy: jax.Array, w: jax.Array, *,
                          stride, padding=(0, 0), dilation=(1, 1),
                          z: jax.Array | None = None, epilogue=None,
                          cin_tile: int | None = None,
                          cout_tile: int | None = None,
                          tap_unroll: int | None = None,
                          interpret: bool):
    """(ddy, dW) of the transposed conv z = tconv(dy, w) w.r.t. cotangent
    g, in a SINGLE `pallas_call` with two output refs.

    g:  (B, Nh, Nw, Cin) cotangent of z (the x-side shape) -- fetched
        ONCE; each tap gather feeds both the conv(g, w) matmul (ddy) and
        the filter-gradient matmul against dy (dW).
    dy: (B, Oh, Ow, Cout) the transposed conv's own input (residual).
    w:  (Kh, Kw, Cin, Cout) forward-orientation filter.
    Returns (ddy (B, Oh, Ow, Cout), dW (Kh, Kw, Cin, Cout)).

    With `epilogue` (static `Epilogue`) this is the VJP of the
    epilogue-fused transposed conv: `z` is its forward output residual,
    act'(z) masks the resident g block in-VMEM before the shared tap
    gathers, and when the epilogue carries a bias its gradient (over the
    tconv OUTPUT channels, Cin) is the launch's third output -- the
    return becomes (ddy, dW, db|None).
    """
    sh, sw = _pair(stride)
    ph, pw_ = _pair(padding)
    dil_h, dil_w = _pair(dilation)
    B, Nh, Nw, Cin = g.shape
    _, Oh, Ow, Cout = dy.shape
    Kh, Kw, _, _ = w.shape
    spec = ConvSpec.make(stride=(sh, sw), padding=(ph, pw_),
                         filter_shape=(Kh, Kw), dilation=(dil_h, dil_w))
    if spec.out_size((Nh, Nw)) != (Oh, Ow):
        raise ValueError(
            f"dy spatial {dy.shape[1:3]} inconsistent with cotangent "
            f"spatial {g.shape[1:3]} for stride={spec.stride}, "
            f"padding={spec.padding}, filter={spec.filter_shape}, "
            f"dilation={spec.dilation}: forward yields "
            f"{spec.out_size((Nh, Nw))}")
    T = Kh * Kw

    if epilogue is not None and epilogue.is_identity:
        epilogue = None
    has_z = epilogue is not None and epilogue.needs_y
    has_db = epilogue is not None and epilogue.bias
    if has_z and z is None:
        raise ValueError("epilogue has an activation but no forward "
                         "output residual z was given")
    if None in (cin_tile, cout_tile, tap_unroll):
        plan = tiling.plan_tiles("ct_backward", spec, x_shape=g.shape,
                                 dy_shape=dy.shape,
                                 itemsize=g.dtype.itemsize,
                                 interpret=interpret, epilogue=epilogue)
        cin_tile = plan.cin_tile if cin_tile is None else cin_tile
        cout_tile = plan.cout_tile if cout_tile is None else cout_tile
        tap_unroll = plan.tap_unroll if tap_unroll is None else tap_unroll
    ci_t = min(cin_tile, Cin)
    co_t = min(cout_tile, Cout)
    n_ci, n_co = -(-Cin // ci_t), -(-Cout // co_t)

    gp = jnp.pad(g, ((0, 0), (ph, ph), (pw_, pw_), (0, 0)))
    gp = pad_to_tap_windows(gp, stride=(sh, sw), dilation=(dil_h, dil_w),
                            k=(Kh, Kw), out_size=(Oh, Ow))
    hp, wp = gp.shape[1], gp.shape[2]
    w_taps = w.reshape(T, Cin, Cout)
    dy_p = dy
    if Cin % ci_t:
        gp = jnp.pad(gp, ((0, 0),) * 3 + ((0, n_ci * ci_t - Cin),))
        w_taps = jnp.pad(w_taps, ((0, 0), (0, n_ci * ci_t - Cin), (0, 0)))
    if Cout % co_t:
        w_taps = jnp.pad(w_taps,
                         ((0, 0), (0, 0), (0, n_co * co_t - Cout)))
        dy_p = jnp.pad(dy_p, ((0, 0),) * 3 + ((0, n_co * co_t - Cout),))
    ci_pad, co_pad = n_ci * ci_t, n_co * co_t

    u = tiling.largest_divisor_leq(T, tap_unroll)
    n_t = T // u
    kern = functools.partial(_ct_bwd_kernel, sh=sh, sw=sw, dil_h=dil_h,
                             dil_w=dil_w, oh=Oh, ow=Ow, kwf=Kw, u=u,
                             n_t=n_t, n_b=B, n_ci=n_ci, n_co=n_co,
                             ci_t=ci_t, co_t=co_t, ep=epilogue,
                             has_z=has_z, has_db=has_db)
    in_specs = [
        pl.BlockSpec((1, hp, wp, ci_t),
                     lambda b, ci, co, t: (b, 0, 0, ci)),
        pl.BlockSpec((u, ci_t, co_t),
                     lambda b, ci, co, t: (t, ci, co)),
        pl.BlockSpec((1, Oh, Ow, co_t),
                     lambda b, ci, co, t: (b, 0, 0, co)),
    ]
    ins = [gp, w_taps, dy_p]
    if has_z:
        # z rides next to g with the identical padding/blocking so the
        # mask multiply is pure resident-block elementwise work.
        zp = jnp.pad(z, ((0, 0), (ph, ph), (pw_, pw_), (0, 0)))
        zp = pad_to_tap_windows(zp, stride=(sh, sw),
                                dilation=(dil_h, dil_w), k=(Kh, Kw),
                                out_size=(Oh, Ow))
        if Cin % ci_t:
            zp = jnp.pad(zp, ((0, 0),) * 3 + ((0, ci_pad - Cin),))
        in_specs.append(pl.BlockSpec((1, hp, wp, ci_t),
                                     lambda b, ci, co, t: (b, 0, 0, ci)))
        ins.append(zp)
    out_specs = [
        pl.BlockSpec((1, Oh, Ow, co_pad),
                     lambda b, ci, co, t: (b, 0, 0, 0)),
        pl.BlockSpec((T, ci_pad, co_pad),
                     lambda b, ci, co, t: (0, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, Oh, Ow, co_pad), jnp.float32),
        jax.ShapeDtypeStruct((T, ci_pad, co_pad), jnp.float32),
    ]
    if has_db:
        out_specs.append(pl.BlockSpec((1, ci_pad),
                                      lambda b, ci, co, t: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((1, ci_pad), jnp.float32))
    outs = pl.pallas_call(
        kern,
        grid=(B, n_ci, n_co, n_t),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        compiler_params=tiling.compiler_params(),
    )(*ins)
    ddy, dw_flat = outs[0], outs[1]
    if Cout % co_t:
        ddy = ddy[..., :Cout]
    if Cin % ci_t or Cout % co_t:
        dw_flat = dw_flat[:, :Cin, :Cout]
    dw = dw_flat.reshape(Kh, Kw, Cin, Cout).astype(g.dtype)
    if epilogue is None:
        return ddy.astype(dy.dtype), dw
    db = outs[2][0, :Cin].astype(g.dtype) if has_db else None
    return ddy.astype(dy.dtype), dw, db


# ---------------------------------------------------------------------------
# autotune runners
# ---------------------------------------------------------------------------

def _backward_runner(spec: ConvSpec, x_shape, dy_shape, epilogue=None):
    """Autotune hook: execute the fused dual-gradient kernel at one
    candidate plan."""
    x = jnp.zeros(x_shape, jnp.float32)
    dy = jnp.zeros(dy_shape, jnp.float32)
    w = jnp.zeros(spec.filter_shape + (x_shape[-1], dy_shape[-1]),
                  jnp.float32)
    y = (jnp.zeros(dy_shape, jnp.float32)
         if epilogue is not None and epilogue.needs_y else None)
    from repro.kernels.ops import interpret_mode
    interp = interpret_mode()

    def run(plan: tiling.TilePlan):
        return jax.block_until_ready(conv_backward_pallas(
            x, dy, w, stride=spec.stride, padding=spec.padding,
            n_out=(x_shape[1], x_shape[2]), dilation=spec.dilation,
            y=y, epilogue=epilogue,
            cin_tile=plan.cin_tile, cout_tile=plan.cout_tile,
            tap_unroll=plan.tap_unroll, phase_unroll=plan.phase_unroll,
            interpret=interp))

    return run


def _ct_backward_runner(spec: ConvSpec, x_shape, dy_shape, epilogue=None):
    """Autotune hook for the transposed-conv fused backward."""
    g = jnp.zeros(x_shape, jnp.float32)
    dy = jnp.zeros(dy_shape, jnp.float32)
    w = jnp.zeros(spec.filter_shape + (x_shape[-1], dy_shape[-1]),
                  jnp.float32)
    z = (jnp.zeros(x_shape, jnp.float32)
         if epilogue is not None and epilogue.needs_y else None)
    from repro.kernels.ops import interpret_mode
    interp = interpret_mode()

    def run(plan: tiling.TilePlan):
        return jax.block_until_ready(tconv_backward_pallas(
            g, dy, w, stride=spec.stride, padding=spec.padding,
            dilation=spec.dilation, z=z, epilogue=epilogue,
            cin_tile=plan.cin_tile,
            cout_tile=plan.cout_tile, tap_unroll=plan.tap_unroll,
            interpret=interp))

    return run


tiling.register_autotune_runner("backward", _backward_runner)
tiling.register_autotune_runner("ct_backward", _ct_backward_runner)
