"""Pallas TPU kernel: fused zero-free transposed conv, stride x dilation
general -- the unified (phase, tap) input-gradient kernel.

ONE `pallas_call` computes the input gradient of a forward conv with ANY
(stride S, filter dilation D) pair.  The decomposition composes the
stride-phase view of the plain transposed conv with the per-tap
enumeration of the dilated-forward kernel:

    dx[i*S + kx*D - P] += dy[i] . W[kx]^T

so tap kx lands in output residue class (kx*D) mod S.  Residues repeat
with period S/gcd(S, D) in kx, hence taps group by kx mod period; within
residue class `a`, tap kx = a + u*period lands on phase row
m = i + (a*D)//S + u*(D/gcd) -- each phase is a stride-1 correlation of
dy with a (D/gcd)-dilated sub-filter.  At D == 1 (period == S, step == 1)
this IS the classic EcoFlow stride-phase decomposition; at S == 1 it is
the self-adjoint per-tap atrous form; in between it is the general
strided+dilated transposed conv that previously fell back to the
multi-launch XLA scatter path.  No dilation zero of either kind (stride
upsampling or filter dilation) is ever stored, moved, or multiplied.

TPU mapping (the EcoFlow -> MXU translation, see DESIGN.md Sec. 2/2.5):
  * the paper's phase enumeration (symbolic outer product grouped by
    output residue) becomes the phase grid axis;
  * the per-tap multicast group becomes a window read (`pl.ds`) of the
    VMEM-resident padded dy block at the tap's (base + u*step) offset;
  * the vertical psum chain becomes the fp32 accumulator tile, summed
    sequentially over the (Cout-tile, tap) grid axes;
  * grouping/expansion onto the array becomes channel tiling.

BlockSpec tiling: grid (B, T/pu, R, Cin_t, Cout_t, TK/u) with T = non-empty
phases, R = row bands of each phase plane, TK = taps per phase (pu
phases x u taps unroll per step -- static window offsets when a single
step remains); per grid step the kernel holds
  dy block  (1, Hp, Wp, Co_t)       -- padded once; index map (b, co)
                                       only, so it is NOT re-fetched
                                       across the band or tap axes
  w block   (pu, u, Co_t, Ci_t)     -- this step's packed (phase, tap)s
  out block (1, pu, band, wo, Ci_t) -- fp32 accumulator across (co, tap)
in VMEM.  A band is the whole phase plane (R = 1) unless its window would
pass the planner's compiled row cap (`tiling.phase_bands`).  Neither
block scales with full channel depth: dy carries a Cout tile and the
output a Cin tile, with extents chosen per geometry by
`kernels/tiling.py` (DESIGN.md Sec. 2.6).  Output
is phase-major (B, T, ho, wo, Cin); host-side assembly places each phase
plane at its stride residue (a gather -- identity at D == 1) and
interleaves with one reshape/transpose, exactly as before.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import ecoflow
from repro.core.spec import ConvSpec, _pair
from repro.kernels import tiling
from repro.kernels.tap_gather import read_window, split_index


def pack_phase_filters(w: jax.Array, stride, dilation=(1, 1)) -> jax.Array:
    """Pack the rotated per-phase sub-filters into one uniform tensor.

    w: (Kh, Kw, Cin, Cout) forward filter ->
    (TPh*TPw, KP, KQ, Cout, Cin) with TP = min(K, period),
    KP = ceil(K/period), period = S/gcd(S, D) per axis.

    The rotation convention (180deg flip + Cout->Cin channel transpose)
    comes from `ecoflow.phase_subfilters` -- the single source of truth
    shared with the dense XLA backend -- applied at the tap-grouping
    PERIOD rather than the stride (they coincide at dilation 1); this
    function only adds the uniform-shape packing: each already-flipped
    sub-filter is zero-padded at the FRONT taps (front-pad-after-flip ==
    tail-pad-before-flip, the identity `tests/test_kernels.py` pins).
    After the flip + front-pad, slot uf of phase `a` holds tap
    kx = a + (KP-1-uf)*period (zero when kx >= K).  Only the non-empty
    phases are packed: residue classes beyond the filter extent
    (period > K) are structural zeros of the upsampling -- the wrapper
    zero-fills their output rows host-side instead of spending grid steps
    on all-zero sub-filters.  The intra-phase tap padding of ragged
    phases (K % period != 0) stays: it costs O(K^2) extra weight words
    per phase, not the O(N^2 S^2) dilation zeros the dataflow eliminates,
    and buys a uniform single-launch grid.
    """
    sh, sw = _pair(stride)
    dh, dw = _pair(dilation)
    Kh, Kw, _, _ = w.shape
    spec = ConvSpec.make(stride=(sh, sw), filter_shape=(Kh, Kw),
                         dilation=(dh, dw))
    per_h, per_w = spec.tap_phase_period
    KP, KQ = spec.taps_per_phase
    subs = ecoflow.phase_subfilters(w, (per_h, per_w))
    phases = []
    for a in range(min(per_h, Kh)):
        for b in range(min(per_w, Kw)):
            sub = subs[a][b]                         # (kp, kq, Cout, Cin)
            kp, kq = sub.shape[0], sub.shape[1]
            sub = jnp.pad(sub, ((KP - kp, 0), (KQ - kq, 0), (0, 0), (0, 0)))
            phases.append(sub)
    return jnp.stack(phases)


def assemble_phase_major(out: jax.Array, spec: ConvSpec, *, n_out,
                         full_size, fill: jax.Array | None = None
                         ) -> jax.Array:
    """Phase-major kernel output (B, T, ho, wo, Cin) -> dx (B, Nh, Nw,
    Cin): place each phase plane at its stride residue with a static
    gather (identity at D == 1 with S <= K; residues outside the image
    are structural zeros of the upsampling), interleave with one
    reshape/transpose chain (rows of dx_full are r = m*S + p <-> (m, p)
    of phase row m), then crop padding / zero-pad non-exact-fit tails.
    Shared by `tconv_fused_pallas` and the fused dual-gradient backward
    (kernels/dconv_backward.py) so the residue-interleave logic cannot
    drift between them.

    `fill` ((Cin,) vector): value taken by positions NO tap reaches
    (structural-zero residues, non-exact-fit tails).  With a fused
    epilogue those positions are epilogue(0) = act(bias), not 0 -- the
    kernel only ever sees real phase planes, so the assembly supplies it.
    None keeps the plain zero-fill."""
    B, _, ho, wo, cin = out.shape
    sh, sw = spec.stride
    ph, pw = spec.padding
    nh, nw = n_out
    fh, fw = full_size
    tph, tpw = spec.n_tap_phases
    out = out.reshape(B, tph, tpw, ho, wo, cin)
    idx_h = [tph] * sh   # sentinel TPh/TPw -> all-zero plane
    for a in range(tph):
        idx_h[spec.tap_phase_residue(a, 0)] = a
    idx_w = [tpw] * sw
    for b in range(tpw):
        idx_w[spec.tap_phase_residue(b, 1)] = b
    if (tph, tpw) != (sh, sw) or idx_h != list(range(sh)) \
            or idx_w != list(range(sw)):
        if fill is None:
            out = jnp.pad(out, ((0, 0), (0, 1), (0, 1)) + ((0, 0),) * 3)
        else:
            fv = fill.astype(out.dtype)
            out = jnp.concatenate(
                [out, jnp.broadcast_to(fv, (B, 1, tpw, ho, wo, cin))],
                axis=1)
            out = jnp.concatenate(
                [out, jnp.broadcast_to(fv, (B, tph + 1, 1, ho, wo, cin))],
                axis=2)
        out = jnp.take(out, jnp.asarray(idx_h), axis=1)
        out = jnp.take(out, jnp.asarray(idx_w), axis=2)
    dx_full = out.transpose(0, 3, 1, 4, 2, 5).reshape(
        B, ho * sh, wo * sw, cin)[:, :fh, :fw, :]
    # Non-exact-fit inputs (forward ignored tail rows/cols): pad tail with
    # the fill value (zero on the plain path).
    eh, ew = max(0, ph + nh - fh), max(0, pw + nw - fw)
    if eh or ew:
        if fill is None:
            dx_full = jnp.pad(dx_full, ((0, 0), (0, eh), (0, ew), (0, 0)))
        else:
            fv = fill.astype(dx_full.dtype)
            h = dx_full.shape[1]
            if eh:
                dx_full = jnp.concatenate(
                    [dx_full, jnp.broadcast_to(
                        fv, (B, eh, dx_full.shape[2], cin))], axis=1)
            if ew:
                dx_full = jnp.concatenate(
                    [dx_full, jnp.broadcast_to(
                        fv, (B, h + eh, ew, cin))], axis=2)
    return dx_full[:, ph:ph + nh, pw:pw + nw, :]


def _fused_tap_kernel(dy_ref, w_ref, *refs, tpw: int, kp: int, kq: int,
                      kh: int, kwf: int, per_h: int, per_w: int,
                      sh: int, sw: int, dh: int, dw: int, step_h: int,
                      step_w: int, pad_h: int, pad_w: int, ho: int, wo: int,
                      pu: int, n_t: int, u: int, n_k: int, n_sp: int,
                      seq1: bool, ep=None):
    """`pu` phases x `u` taps per sequential grid step: read each tap's
    window out of the VMEM-resident padded dy block, one MXU
    matmul per tap with its (Cout_t, Cin_t) weights, accumulate each
    phase's fp32 tile across the (Cout-tile, tap-step) axes.  A step
    computes `ho` rows of each phase plane: band `program_id(2)` of
    `n_sp` (the whole plane when n_sp == 1).
    When a single (phase, tap) grid step remains, every window offset is
    a python int and the reads are STATIC -- and the
    zero-padded slots of ragged phases (slot tap index kx >= K) are
    SKIPPED outright via the shared (phase, slot) -> filter-tap validity
    test, the same static skip the fused backward kernel applies
    (dconv_backward.py); on partially unrolled grids the slot index is
    traced, so padded slots fall back to multiplying by zero and the step
    body stays uniform across phases.

    refs = ([bias_ref,] out_ref); `ep` fuses act(scale * . + bias) onto
    each finished phase plane before its HBM store."""
    bias_ref = refs[0] if len(refs) == 2 else None
    out_ref = refs[-1]
    ts = pl.program_id(1) if n_t > 1 else 0
    r0 = pl.program_id(2) * ho if n_sp > 1 else 0
    co = pl.program_id(4)
    ks = pl.program_id(5) if n_k > 1 else 0
    traced = n_t > 1 or n_k > 1
    # seq1: single sequential (Cout-tile, tap) step -> every visit to an
    # out block is its first, the predication compiles away.
    first = None if seq1 else (
        (co == 0) if n_k == 1 else ((co == 0) & (pl.program_id(5) == 0)))
    last = None
    if ep is not None and not seq1:
        last = (co == pl.num_programs(4) - 1)
        if n_k > 1:
            last &= pl.program_id(5) == n_k - 1

    def _tail(vals):
        return ep.apply(vals, None if bias_ref is None else bias_ref[0])

    for p in range(pu):
        a, b = split_index(ts, pu, p, tpw)
        acc = None
        for j in range(u):
            uf, vf = split_index(ks, u, j, kq)
            if not traced:
                # Static slot: skip padding slots of ragged phases -- the
                # slot's filter tap falls outside the K x K extent, its
                # packed weights are structurally zero.
                kx = a + (kp - 1 - uf) * per_h
                ky = b + (kq - 1 - vf) * per_w
                if kx >= kh or ky >= kwf:
                    continue
            # Flipped-slot tap index u' = KP-1-uf (see
            # pack_phase_filters): window offset base(a) + u'*step,
            # shifted into the padded frame.
            start_h = pad_h - (a * dh) // sh - (kp - 1 - uf) * step_h
            start_w = pad_w - (b * dw) // sw - (kq - 1 - vf) * step_w
            win = read_window(dy_ref, (0,), r0 + start_h, start_w, oh=ho,
                              ow=wo)
            lhs = win.reshape(ho * wo, win.shape[-1]).astype(jnp.float32)
            rhs = w_ref[p, j].astype(jnp.float32)    # (co_t, ci_t)
            prod = jax.lax.dot(lhs, rhs,
                               preferred_element_type=jnp.float32)
            acc = prod if acc is None else acc + prod
        acc = acc.reshape(ho, wo, out_ref.shape[-1])
        if first is None:
            out_ref[0, p] = _tail(acc) if ep is not None else acc
        else:
            @pl.when(first)
            def _init(p=p, acc=acc):
                out_ref[0, p] = acc

            @pl.when(jnp.logical_not(first))
            def _acc(p=p, acc=acc):
                out_ref[0, p] += acc

            if ep is not None:
                @pl.when(last)
                def _epilogue(p=p):
                    out_ref[0, p] = _tail(out_ref[0, p])


@functools.partial(jax.jit, static_argnames=("stride", "padding", "n_out",
                                             "dilation", "cin_tile",
                                             "cout_tile", "tap_unroll",
                                             "phase_unroll", "spatial_tile",
                                             "interpret", "epilogue"))
def tconv_fused_pallas(dy: jax.Array, w: jax.Array, *, stride, padding=(0, 0),
                       n_out=None, dilation=(1, 1),
                       bias: jax.Array | None = None,
                       epilogue=None,
                       cin_tile: int | None = None,
                       cout_tile: int | None = None,
                       tap_unroll: int | None = None,
                       phase_unroll: int | None = None,
                       spatial_tile: int | None = None,
                       interpret: bool) -> jax.Array:
    """Zero-free transposed conv in a SINGLE `pallas_call`, any (S, D).

    dy: (B, Oh, Ow, Cout) error / generator input.
    w:  (Kh, Kw, Cin, Cout) forward filter (undilated taps; `dilation` is
        the forward filter dilation D whose adjoint this computes).
    Returns (B, Nh, Nw, Cin) where (Nh, Nw) = n_out (default exact fit).
    Channel tiles default to the geometry-aware planner in
    `kernels/tiling.py`; pass them explicitly to pin a tiling.
    `spatial_tile` (dy rows) splits each phase plane into row bands
    (`tiling.phase_bands`); None with pinned tiles keeps whole planes.

    `epilogue` (static `Epilogue`) fuses act(scale * . + bias) onto each
    finished phase plane in VMEM; `bias` is the (Cin,) vector (the tconv
    OUTPUT channels) when the epilogue carries one.  Positions no tap
    reaches take the value epilogue(0) via the assembly fill.
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
    step_h, step_w = spec.tap_phase_step
    TPh, TPw = spec.n_tap_phases
    KP, KQ = spec.taps_per_phase
    T, TK = TPh * TPw, KP * KQ

    w_packed = pack_phase_filters(w, (sh, sw), (dh, dw))
    # (T, KP, KQ, Cout, Cin) -> flat tap axis for the (t, k) block index.
    w_flat = w_packed.reshape(T, TK, Cout, Cin)

    ho, wo = -(-Fh // sh), -(-Fw // sw)  # uniform phase-plane extent

    if epilogue is not None and epilogue.is_identity:
        epilogue = None
    if epilogue is not None and epilogue.bias and bias is None:
        raise ValueError("epilogue.bias=True but no bias array was given")
    if None in (cin_tile, cout_tile, tap_unroll, phase_unroll):
        plan = tiling.plan_tiles("input_grad", spec,
                                 x_shape=(B, Nh, Nw, Cin),
                                 dy_shape=dy.shape,
                                 itemsize=dy.dtype.itemsize,
                                 interpret=interpret, epilogue=epilogue)
        cin_tile = plan.cin_tile if cin_tile is None else cin_tile
        cout_tile = plan.cout_tile if cout_tile is None else cout_tile
        tap_unroll = plan.tap_unroll if tap_unroll is None else tap_unroll
        phase_unroll = plan.phase_unroll if phase_unroll is None \
            else phase_unroll
        spatial_tile = plan.spatial_tile if spatial_tile is None \
            else spatial_tile
    band, n_sp = tiling.phase_bands(Oh, ho, spatial_tile or Oh)
    # Pad dy ONCE (uniform across phases): front by the largest tap offset
    # base(TPh-1) + (KP-1)*step, tail so every band's windows fit.
    pad_h = spec.tap_phase_base(TPh - 1, 0) + (KP - 1) * step_h
    pad_w = spec.tap_phase_base(TPw - 1, 1) + (KQ - 1) * step_w
    dy_pad = jnp.pad(dy, ((0, 0), (pad_h, n_sp * band - Oh),
                          (pad_w, wo - Ow), (0, 0)))
    hp, wp = dy_pad.shape[1], dy_pad.shape[2]
    ci_t = min(cin_tile, Cin)
    co_t = min(cout_tile, Cout)
    n_ci, n_co = -(-Cin // ci_t), -(-Cout // co_t)
    if Cout % co_t:
        dy_pad = jnp.pad(dy_pad, ((0, 0),) * 3 + ((0, n_co * co_t - Cout),))
        w_flat = jnp.pad(w_flat, ((0, 0),) * 2 +
                         ((0, n_co * co_t - Cout), (0, 0)))
    if Cin % ci_t:
        w_flat = jnp.pad(w_flat, ((0, 0),) * 3 + ((0, n_ci * ci_t - Cin),))

    u = tiling.largest_divisor_leq(TK, tap_unroll)
    pu = tiling.largest_divisor_leq(T, phase_unroll)
    n_k, n_t = TK // u, T // pu
    per_h, per_w = spec.tap_phase_period
    kern = functools.partial(_fused_tap_kernel, tpw=TPw, kp=KP, kq=KQ,
                             kh=Kh, kwf=Kw, per_h=per_h, per_w=per_w,
                             sh=sh, sw=sw, dh=dh, dw=dw, step_h=step_h,
                             step_w=step_w, pad_h=pad_h, pad_w=pad_w,
                             ho=band, wo=wo, pu=pu, n_t=n_t, u=u,
                             n_k=n_k, n_sp=n_sp,
                             seq1=(n_co == 1 and n_k == 1), ep=epilogue)
    in_specs = [
        pl.BlockSpec((1, hp, wp, co_t),
                     lambda b, t, r, ci, co, k: (b, 0, 0, co)),
        pl.BlockSpec((pu, u, co_t, ci_t),
                     lambda b, t, r, ci, co, k: (t, k, co, ci)),
    ]
    ins = [dy_pad, w_flat]
    if epilogue is not None and epilogue.bias:
        bp = bias.astype(jnp.float32).reshape(1, Cin)
        if Cin % ci_t:
            bp = jnp.pad(bp, ((0, 0), (0, n_ci * ci_t - Cin)))
        in_specs.append(pl.BlockSpec((1, ci_t),
                                     lambda b, t, r, ci, co, k: (0, ci)))
        ins.append(bp)
    out = pl.pallas_call(
        kern,
        grid=(B, n_t, n_sp, n_ci, n_co, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, pu, band, wo, ci_t),
                               lambda b, t, r, ci, co, k: (b, t, r, 0, ci)),
        out_shape=jax.ShapeDtypeStruct((B, T, n_sp * band, wo, n_ci * ci_t),
                                       jnp.float32),
        interpret=interpret,
        compiler_params=tiling.compiler_params(),
    )(*ins)

    if Cin % ci_t:   # slice only when channel padding occurred
        out = out[..., :Cin]
    # Structural-zero residues / tail positions never reach the kernel:
    # under an epilogue their value is epilogue(0) = act(bias), nonzero
    # only when a bias rides along (every supported activation fixes 0).
    fill = None
    if epilogue is not None and epilogue.bias:
        fill = epilogue.apply(jnp.zeros((Cin,), jnp.float32), bias)
    return assemble_phase_major(out, spec, n_out=(Nh, Nw),
                                full_size=(Fh, Fw),
                                fill=fill).astype(dy.dtype)


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
        return jax.block_until_ready(tconv_fused_pallas(
            dy, w, stride=spec.stride, padding=spec.padding, n_out=n_out,
            dilation=spec.dilation, bias=bias, epilogue=epilogue,
            cin_tile=plan.cin_tile,
            cout_tile=plan.cout_tile, tap_unroll=plan.tap_unroll,
            phase_unroll=plan.phase_unroll, spatial_tile=plan.spatial_tile,
            interpret=interp))

    return run


tiling.register_autotune_runner("input_grad", _autotune_runner)
