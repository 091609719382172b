"""Geometry-aware tile selection for the Pallas conv kernels.

Every fused kernel in this package used to hard-code 128-wide channel
tiles (`tile: int = 128`) regardless of geometry -- the right call for a
ResNet trunk, the wrong one for a 3-channel stem, a 29-channel
ShuffleNet block, or an 11x11 AlexNet filter whose tap loop then costs
121 grid launch-steps.  This module makes the tiling a *function of the
geometry*: given a `ConvSpec`, the operand shapes, the dtype, and a VMEM
budget, `plan_tiles` returns a `TilePlan` -- channel tiles, an output-row
(spatial) tile, a tap-unroll factor, and the grid order -- from an
analytical working-set / traffic model (CARLA-style per-layer
reconfigurable tiling, expressed for a BlockSpec machine).

Two modes:

  * **analytical** (default): enumerate the candidate tilings whose VMEM
    working set fits the budget, score each by modeled HBM traffic (block
    re-streams under the kernel's index maps) plus a per-grid-step launch
    cost, and pick the cheapest.  The step cost is weighted heavily in
    interpret mode (where per-step dispatch dominates wall clock) and
    lightly for compiled TPU execution (where traffic dominates).
  * **autotune** (`ECOFLOW_TILING=autotune` or `mode="autotune"`): sweep
    the same candidate set empirically -- each kernel module registers a
    runner that executes the real kernel at a candidate plan -- timing
    with `benchmarks.wallclock._time` (median-of-iters) when the
    benchmarks package is importable, else a local fallback with the same
    semantics.  Winners persist to a JSON cache keyed by (op, geometry)
    (`ECOFLOW_TILE_CACHE`, default `.ecoflow_cache/tile_cache.json` in
    the checkout) so a sweep is paid once per geometry per host.

Beyond tiles, the planner picks the *strategy*: `plan_strategy` races the
phase decomposition against the predicated implicit-GEMM formulation
(kernels/implicit_gemm.py) per geometry and returns `(strategy,
TilePlan)`.  The analytical race extends the tile score with a
predicated-lane waste term -- the masked-MAC fraction of the flat GEMM,
exact from the `ConvSpec` geometry via `ecoflow.predicated_mac_fraction`
-- against the phase path's scheduled-tap count and host-side assembly
traffic; autotune mode sweeps BOTH strategies' candidate sets through
their registered runners.  `ECOFLOW_STRATEGY=phase|implicit_gemm|auto`
forces or frees the choice per process (auto is the default), and the
strategy is part of every cache key (memoized and on-disk), so a flip
re-plans instead of serving a stale winner.  See DESIGN.md Sec. 2.10.

The model's constraints encode the kernels' invariants rather than
guessing at them:

  * the working set is computed from the kernels' actual block shapes
    (doubled for the in/out streams, Pallas double-buffers blocks);
  * unrolled taps are consumed one matmul at a time against the resident
    blocks (never a concatenated K^2-replicated tap stack -- peak
    intermediate stays bounded by a small multiple of the padded input,
    pinned by
    `tests/test_dispatch.py::test_filter_grad_memory_not_k2_replicated`),
    and compiled-mode unrolling is capped at `MAX_TAP_UNROLL_COMPILED`
    because Mosaic kernel code size, not VMEM, is the binding constraint;
  * channel tiles prefer the exact channel count when it is small enough
    to fit (no host-side pad/slice at all) and MXU-aligned powers of two
    otherwise -- in compiled mode only the full extent or a multiple of
    128 lanes, the only channel blocks Mosaic accepts;
  * a compiled launch's working set counts each channel axis at its
    128-lane VMEM layout width (a 3-channel block occupies 128 lanes).

See DESIGN.md Sec. 2.6 for the policy rules and the cache format.
"""
from __future__ import annotations

import dataclasses
import functools
import inspect
import json
import math
import os
import pathlib
import warnings
from typing import Callable, Dict, Optional

from repro.core import ecoflow
from repro.core.spec import ConvSpec, Epilogue
from repro.runtime import REPO_ROOT

# Fraction of a TPU core's ~16 MiB VMEM the planner budgets for one
# kernel's resident blocks (the rest covers double-buffering slack,
# scalar state, and the compiler's own scratch).  Overridable per call
# and via ECOFLOW_VMEM_BUDGET (bytes).
DEFAULT_VMEM_BUDGET = 8 * 2 ** 20

# Scoped-VMEM limit every compiled conv kernel is built with.  The
# compiler's default (16 MiB) cannot hold the full-frame blocks of a
# 128x128 image once a narrow channel axis is padded to 128 lanes, plus
# Mosaic's own spill space for the per-tap matmul operands; a v5e core
# has 128 MiB of VMEM.  The planner still ranks candidates against
# DEFAULT_VMEM_BUDGET -- this is headroom, not a bigger plan.
VMEM_LIMIT_BYTES = 64 * 2 ** 20

# VMEM lane width: the minor (channel) dim of every block is laid out in
# multiples of it on the chip.
LANES = 128

# Modeled cost of one grid step, in traffic-equivalent bytes.  The
# interpret emulation re-materializes every block and re-dispatches the
# kernel body per step, so steps are expensive; compiled TPU steps cost
# roughly a DMA descriptor + pipeline bubble.
STEP_COST_INTERPRET = 1 << 18
STEP_COST_COMPILED = 1 << 12

# Compiled-mode cap on taps unrolled per grid step: each unrolled tap is
# a distinct matmul in the kernel body, and Mosaic code size (not VMEM)
# is the binding constraint.  Interpret mode has no code-size limit and
# profits most from single-step launches, so it may unroll fully.
MAX_TAP_UNROLL_COMPILED = 16

# Compiled-mode cap on the rows (H x W sites) of one tap window of the
# unified phase kernel: Mosaic unrolls each window's matmul and its
# relayouts into vector ops, so code size and compile time grow with it.
# A phase plane past the cap is computed in row bands (`phase_bands`);
# the compile-time table is in PERF.md.
MAX_WINDOW_ROWS_COMPILED = 1024

OPS = ("filter_grad", "forward", "input_grad", "backward", "ct_backward")

# Kernel strategies the planner races per geometry.  "phase" is the
# EcoFlow phase decomposition (every op family has a phase kernel);
# "implicit_gemm" is the predicated flat-GEMM formulation
# (kernels/implicit_gemm.py), currently implemented for the standalone
# input gradient only -- the fused dual-gradient backward stays
# phase-decomposed, and `plan_strategy` falls back per op.
STRATEGIES = ("phase", "implicit_gemm")

# Strategy-race weights, in traffic-equivalent bytes.  MAC_COST prices
# one scheduled MXU MAC slot -- predicated (masked) implicit-GEMM lanes
# and the phase path's ragged-slot padding both pay it.  Compiled MACs
# flow through the 128x128 systolic array (cheap per slot but real:
# high-waste geometries like AlexNet S=4 must lose the race); interpret
# MACs run on the host BLAS behind a per-step dispatch that dominates,
# so the slot price is lower.  ASSEMBLY_PASSES charges the phase path's
# host-side residue interleave: the phase-major output tensor is
# rematerialized ~3x by the pad/take/transpose/reshape chain
# (assemble_phase_major) -- traffic the implicit-GEMM path never spends.
MAC_COST_COMPILED = 1 / 32
MAC_COST_INTERPRET = 1 / 64
ASSEMBLY_PASSES = 3


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """One kernel launch's tiling decision.

    cin_tile / cout_tile -- channel block extents (<= actual channels;
        equal to them when the planner found the exact count cheapest,
        in which case the kernels skip the pad/slice entirely).
    spatial_tile -- output rows per block (Oh for the filter gradient;
        dy rows per row band for the phase input gradient, `phase_bands`;
        kernels that do not spatially tile carry their full extent here).
    tap_unroll -- taps computed per grid step (a divisor of the tap
        count; 1 = one tap per step, T = all taps in one step).
    phase_unroll -- stride phases computed per grid step of the unified
        input-gradient kernel (a divisor of the phase count; other
        kernels have no phase axis and carry 1).
    grid_order -- the kernel's grid axes outermost-first, for
        documentation and structural pins.
    source -- "analytical" | "autotune" | "cache".
    """
    cin_tile: int
    cout_tile: int
    spatial_tile: int
    tap_unroll: int = 1
    phase_unroll: int = 1
    grid_order: tuple = ()
    source: str = "analytical"

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["grid_order"] = list(self.grid_order)
        return d


# ---------------------------------------------------------------------------
# Candidate enumeration
# ---------------------------------------------------------------------------

def _channel_candidates(c: int, interpret: bool) -> tuple[int, ...]:
    """Candidate channel-tile extents for a `c`-channel axis: the exact
    count when small enough to be a single unpadded tile, MXU-aligned
    powers of two below it otherwise.  Compiled (Mosaic) blocks need the
    channel extent -- a block's minor dim -- to be the full axis or a
    multiple of 128 lanes, so the narrower splits are interpret-only."""
    cands = {min(c, 256)}
    if c <= 256:
        cands.add(c)  # exact: no pad, no slice
    for p in (256, 128, 64, 32, 16, 8):
        if p < c and (interpret or p % LANES == 0):
            cands.add(p)
    return tuple(sorted(cands, reverse=True))


def compiler_params():
    """Mosaic parameters every conv kernel is built with (ignored in
    interpret mode)."""
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _lanes(c: int) -> int:
    """Channel extent rounded up to the VMEM lane width."""
    return _cdiv(c, LANES) * LANES


def _spatial_candidates(oh: int) -> tuple[int, ...]:
    """Candidate output-row tiles: the full extent, then halvings."""
    cands, v = [], oh
    while v >= 1:
        cands.append(v)
        if v == 1:
            break
        v = -(-v // 2)
    return tuple(dict.fromkeys(cands))


def phase_bands(oh: int, ho: int, spatial_tile: int) -> tuple[int, int]:
    """(band, n_bands) of the unified phase kernel: its `ho`-row phase
    planes split into as many row bands as `spatial_tile` splits the
    `oh` rows of dy, each band `band` rows (the last band may run past
    `ho`; the assembly crops it).  `spatial_tile >= oh` is one band."""
    n = _cdiv(oh, max(1, min(spatial_tile, oh)))
    band = _cdiv(ho, n)
    return band, _cdiv(ho, band)


def _divisors(t: int) -> tuple[int, ...]:
    return tuple(d for d in range(t, 0, -1) if t % d == 0)


def largest_divisor_leq(n: int, request: int) -> int:
    """Largest divisor of `n` that is <= max(1, request): the kernels'
    clamp from a planned unroll factor to one their grid can realize.
    Lives here so the kernel-side clamp and the planner's candidate set
    (which only emits exact divisors) cannot drift apart."""
    request = max(1, min(request, n))
    return max(d for d in range(1, request + 1) if n % d == 0)


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


# ---------------------------------------------------------------------------
# Analytical model: working set + traffic per op family
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class _Geom:
    """Normalized problem geometry shared by the per-op models."""
    spec: ConvSpec
    b: int
    nh: int
    nw: int
    cin: int
    oh: int
    ow: int
    cout: int
    itemsize: int


def strided_forward_packing(spec: ConvSpec, x_shape):
    """The problem `dconv_forward` runs for a forward conv over
    `x_shape`: `(spec, x_shape)` unchanged, or -- undilated, strided, and
    each filter extent a multiple of its stride -- the S x S
    space-to-depth form: a stride-1, unpadded (K/S) x (K/S) conv over
    S_h*S_w*Cin channels of the (O + K/S - 1)-extent packed input, which
    computes the same taps with no strided read (the forward's
    counterpart of the transposed conv's phase decomposition)."""
    sh, sw = spec.stride
    kh, kw = spec.filter_shape
    if spec.dilation != (1, 1) or (sh, sw) == (1, 1) or kh % sh or kw % sw:
        return spec, x_shape
    b, nh, nw, c = x_shape
    oh, ow = spec.out_size((nh, nw))
    kp, kq = kh // sh, kw // sw
    return (ConvSpec.make(filter_shape=(kp, kq)),
            (b, oh + kp - 1, ow + kq - 1, sh * sw * c))


def _geom(op: str, spec: ConvSpec, x_shape, dy_shape, itemsize) -> _Geom:
    if op == "forward":
        spec, x_shape = strided_forward_packing(spec, x_shape)
    b, nh, nw, cin = x_shape
    _, oh, ow, cout = dy_shape
    return _Geom(spec, b, nh, nw, cin, oh, ow, cout, itemsize)


def _padded_input_extent(g: _Geom) -> tuple[int, int]:
    """Tap-window extent of the once-padded input (the x block's spatial
    frame): (O-1)*S + D*(K-1) + 1 per axis."""
    sh, sw = g.spec.stride
    dh, dw = g.spec.dilation
    kh, kw = g.spec.filter_shape
    return ((g.oh - 1) * sh + dh * (kh - 1) + 1,
            (g.ow - 1) * sw + dw * (kw - 1) + 1)


def _filter_grad_model(g: _Geom, ci_t, co_t, sp_t, u, pu=1, ep=None):
    """(ws, traffic, steps, step_blk) for the rebuilt filter-grad kernel:
    grid (Cin_t, Cout_t, B, spatial, tap_steps), out block
    (T, ci_t, co_t) stationary across the sequential (B, spatial, tap)
    accumulation axes.  Tap slices are consumed one at a time (per-tap
    matmuls, no concatenated stack), so the unroll factor adds no
    resident transient."""
    sh, _ = g.spec.stride
    dh, _ = g.spec.dilation
    kh, kw = g.spec.filter_shape
    t = kh * kw
    _, wp = _padded_input_extent(g)
    sp = min(sp_t, g.oh)
    rows_x = (sp - 1) * sh + dh * (kh - 1) + 1
    n_ci, n_co = _cdiv(g.cin, ci_t), _cdiv(g.cout, co_t)
    n_sp, n_t = _cdiv(g.oh, sp), _cdiv(t, u)

    x_blk = rows_x * wp * ci_t * g.itemsize
    dy_blk = sp * g.ow * co_t * g.itemsize
    out_blk = t * ci_t * co_t * 4                      # fp32 accumulator
    ws = 2 * (x_blk + dy_blk) + out_blk + sp * g.ow * ci_t * 4 \
        + ci_t * co_t * 4

    # Compiled traffic (blocks DMA'd on index-map change): x streams once
    # per Cout tile, dy once per Cin tile, out written once.
    traffic = (n_co * (g.b * n_sp * n_ci * x_blk)
               + n_ci * (g.b * n_sp * n_co * dy_blk)
               + t * n_ci * ci_t * n_co * co_t * 4)
    if n_sp > 1:   # host-side overlapping-slab stack: one extra x copy
        traffic += g.b * n_sp * rows_x * wp * g.cin * g.itemsize
    steps = n_ci * n_co * g.b * n_sp * n_t
    return ws, traffic, steps, x_blk + dy_blk


def _forward_model(g: _Geom, ci_t, co_t, sp_t, u, pu=1, ep=None):
    """dconv_forward: grid (B, Cout_t, Cin_t, T/u); x block holds the
    full padded frame at a Cin tile, the w block `u` taps' weights, out
    accumulates over the sequential (Cin_t, tap-step) axes.  An epilogue
    with a bias adds the (1, co_t) bias block to the resident set (the
    activation itself touches only the already-resident out block)."""
    kh, kw = g.spec.filter_shape
    t = kh * kw
    hp, wp = _padded_input_extent(g)
    n_ci, n_co = _cdiv(g.cin, ci_t), _cdiv(g.cout, co_t)
    x_blk = hp * wp * ci_t * g.itemsize
    w_blk = u * ci_t * co_t * g.itemsize
    out_blk = g.oh * g.ow * co_t * 4
    ws = 2 * (x_blk + w_blk) + out_blk + g.oh * g.ow * ci_t * 4
    traffic = (n_co * (g.b * n_ci * x_blk)
               + g.b * t * n_ci * n_co * ci_t * co_t * g.itemsize
               + g.b * g.oh * g.ow * n_co * co_t * 4)
    if ep is not None and ep.bias:
        ws += 2 * co_t * 4
        traffic += n_co * co_t * 4
    steps = g.b * n_co * n_ci * _cdiv(t, u)
    return ws, traffic, steps, x_blk + w_blk


def _phase_frame(spec: ConvSpec, oh: int, ow: int):
    """Padded-dy frame geometry of the unified (phase, tap) kernels
    (tconv_phase and the fused backward): (T phases, TK taps/phase,
    ho, wo phase-plane extent, hp, wp padded frame extent).  One
    definition so the working-set models cannot drift from each other
    (the kernels themselves derive the same quantities from ConvSpec)."""
    tph, tpw = spec.n_tap_phases
    kp, kq = spec.taps_per_phase
    t, tk = tph * tpw, kp * kq
    fh, fw = spec.full_size((oh, ow))
    ho, wo = _cdiv(fh, spec.stride[0]), _cdiv(fw, spec.stride[1])
    pad_h = spec.tap_phase_base(tph - 1, 0) \
        + (kp - 1) * spec.tap_phase_step[0]
    pad_w = spec.tap_phase_base(tpw - 1, 1) \
        + (kq - 1) * spec.tap_phase_step[1]
    return t, tk, ho, wo, pad_h + ho, pad_w + wo


def _input_grad_model(g: _Geom, ci_t, co_t, sp_t, u, pu=1, ep=None):
    """tconv_phase: grid (B, T/pu, R, Cin_t, Cout_t, TK/u); dy block
    holds the full padded frame at a Cout tile, the w block `pu * u`
    packed (phase, tap)s, the out block one row band of `pu` phase planes
    (`phase_bands`: R bands for `sp_t` dy rows); out accumulates over the
    sequential (Cout_t, tap-step) axes.  An epilogue with a bias adds the
    (1, ci_t) bias-over-Cin block (the transposed conv's output channels
    are the forward input channels)."""
    t, tk, ho, wo, hp, wp = _phase_frame(g.spec, g.oh, g.ow)
    band, n_sp = phase_bands(g.oh, ho, sp_t)
    hp += n_sp * band - ho       # dy tail-padded for the last band
    n_ci, n_co = _cdiv(g.cin, ci_t), _cdiv(g.cout, co_t)
    dy_blk = hp * wp * co_t * g.itemsize
    w_blk = pu * u * co_t * ci_t * g.itemsize
    out_blk = pu * band * wo * ci_t * 4
    ws = 2 * (dy_blk + w_blk) + out_blk + band * wo * co_t * 4
    traffic = (g.b * _cdiv(t, pu) * n_sp * n_ci * n_co * dy_blk
               + g.b * t * tk * n_sp * n_ci * n_co * co_t * ci_t
               * g.itemsize
               + g.b * t * n_sp * band * wo * n_ci * ci_t * 4)
    if ep is not None and ep.bias:
        ws += 2 * ci_t * 4
        traffic += n_ci * ci_t * 4
    steps = g.b * _cdiv(t, pu) * n_sp * n_ci * n_co * _cdiv(tk, u)
    return ws, traffic, steps, dy_blk + w_blk


def _backward_model(g: _Geom, ci_t, co_t, sp_t, u, pu=1, ep=None):
    """Fused dual-gradient backward (kernels/dconv_backward.py): grid
    (Cin_t, B, T/pu, Cout_t, TK/u); the dy block holds the full padded
    frame at a Cout tile (the SHARED fetch), the x block the full padded
    input at a Cin tile, and the working set carries BOTH accumulators:
    `pu` phase planes of dx plus the stationary (T_w, ci_t, Cout_pad)
    dW block (full padded Cout width, so the co axis never interrupts
    its visit streak).  An activation epilogue doubles the dy-frame
    residency (the saved output y streams in the SAME padded block shape
    to mask the cotangent in VMEM); a bias epilogue adds the stationary
    (1, Cout_pad) db accumulator as a third output."""
    kh, kw = g.spec.filter_shape
    t, tk, ho, wo, hp, wp = _phase_frame(g.spec, g.oh, g.ow)
    xh, xw = _padded_input_extent(g)
    n_ci, n_co = _cdiv(g.cin, ci_t), _cdiv(g.cout, co_t)
    dy_blk = hp * wp * co_t * g.itemsize
    x_blk = xh * xw * ci_t * g.itemsize
    w_blk = pu * u * co_t * ci_t * g.itemsize
    dx_blk = pu * ho * wo * ci_t * 4
    dw_blk = kh * kw * ci_t * (n_co * co_t) * 4
    ws = 2 * (dy_blk + x_blk + w_blk) + dx_blk + dw_blk \
        + ho * wo * ci_t * 4 + g.oh * g.ow * ci_t * 4 + ci_t * co_t * 4
    # dy stays resident across everything inside (ci, b) when n_co == 1;
    # otherwise it re-streams per (phase-step, co) like tconv.
    dy_streams = g.b * n_ci * (1 if n_co == 1 else _cdiv(t, pu) * n_co)
    traffic = (dy_streams * dy_blk
               + g.b * n_ci * x_blk
               + t * tk * n_ci * n_co * co_t * ci_t * g.itemsize
               + g.b * t * ho * wo * n_ci * ci_t * 4
               + n_ci * kh * kw * ci_t * n_co * co_t * 4)
    if ep is not None:
        if ep.needs_y:                 # y block mirrors the dy block
            ws += 2 * dy_blk
            traffic += dy_streams * dy_blk
        if ep.bias:                    # db third output, constant map
            ws += n_co * co_t * 4
            traffic += n_co * co_t * 4
    steps = n_ci * g.b * _cdiv(t, pu) * n_co * _cdiv(tk, u)
    return ws, traffic, steps, dy_blk + x_blk + w_blk


def _ct_backward_model(g: _Geom, ci_t, co_t, sp_t, u, pu=1, ep=None):
    """Fused transposed-conv backward: grid (B, Cin_t, Cout_t, T/u); the
    g block holds the full padded frame at a Cin tile (the SHARED
    fetch), ddy spans full padded Cout per batch row and dW spans full
    padded channels (constant index map -- one streak over the whole
    grid), so both accumulators are part of every candidate's resident
    working set.  An activation epilogue doubles the g-frame residency
    (the saved transposed-conv output z streams in the same padded block
    shape to mask the cotangent in VMEM); a bias epilogue adds the
    stationary (1, Cin_pad) db accumulator as a third output."""
    kh, kw = g.spec.filter_shape
    t = kh * kw
    hp, wp = _padded_input_extent(g)
    n_ci, n_co = _cdiv(g.cin, ci_t), _cdiv(g.cout, co_t)
    g_blk = hp * wp * ci_t * g.itemsize
    w_blk = u * ci_t * co_t * g.itemsize
    dy_blk = g.oh * g.ow * co_t * g.itemsize
    ddy_blk = g.oh * g.ow * (n_co * co_t) * 4
    dw_blk = t * (n_ci * ci_t) * (n_co * co_t) * 4
    ws = 2 * (g_blk + w_blk + dy_blk) + ddy_blk + dw_blk \
        + g.oh * g.ow * ci_t * 4 + ci_t * co_t * 4
    traffic = (g.b * n_ci * g_blk
               + g.b * n_ci * n_co * dy_blk
               + g.b * t * n_ci * n_co * ci_t * co_t * g.itemsize
               + g.b * g.oh * g.ow * n_co * co_t * 4
               + t * n_ci * ci_t * n_co * co_t * 4)
    if ep is not None:
        if ep.needs_y:                 # z block mirrors the g block
            ws += 2 * g_blk
            traffic += g.b * n_ci * g_blk
        if ep.bias:                    # db third output over Cin
            ws += n_ci * ci_t * 4
            traffic += n_ci * ci_t * 4
    steps = g.b * n_ci * n_co * _cdiv(t, u)
    return ws, traffic, steps, g_blk + w_blk + dy_blk


def _implicit_gemm_model(g: _Geom, ci_t, co_t, sp_t, u, pu=1, ep=None):
    """kernels/implicit_gemm.py: grid (B, Cin_t, Cout_t, T/u); the dy
    block is the UNPADDED (Oh, Ow, Co_t) error tile (resident across the
    tap axis), the w block `u` flat taps' weights, the out block the full
    (Fh, Fw, Ci_t) pre-slice extent accumulated over the sequential
    (Cout_t, tap) axes.  The working set additionally carries the
    in-VMEM zero-interleaved upsampled frame (extent Fh + Dh*(Kh-1) per
    axis) and the per-tap fp32 window product -- the predicated lanes
    live in VMEM, never in HBM traffic."""
    kh, kw = g.spec.filter_shape
    dh, dw = g.spec.dilation
    t = kh * kw
    fh, fw = g.spec.full_size((g.oh, g.ow))
    uh, uw = fh + dh * (kh - 1), fw + dw * (kw - 1)
    n_ci, n_co = _cdiv(g.cin, ci_t), _cdiv(g.cout, co_t)
    dy_blk = g.oh * g.ow * co_t * g.itemsize
    w_blk = u * co_t * ci_t * g.itemsize
    out_blk = fh * fw * ci_t * 4
    ws = 2 * (dy_blk + w_blk) + out_blk \
        + uh * uw * co_t * g.itemsize + fh * fw * co_t * 4 \
        + fh * fw * ci_t * 4
    traffic = (g.b * n_ci * n_co * dy_blk
               + g.b * t * n_ci * n_co * co_t * ci_t * g.itemsize
               + g.b * fh * fw * n_ci * ci_t * 4)
    if ep is not None and ep.bias:
        ws += 2 * ci_t * 4
        traffic += n_ci * ci_t * 4
    steps = g.b * n_ci * n_co * _cdiv(t, u)
    return ws, traffic, steps, dy_blk + w_blk


_MODELS: Dict[str, Callable] = {
    "filter_grad": _filter_grad_model,
    "forward": _forward_model,
    "input_grad": _input_grad_model,
    "backward": _backward_model,
    "ct_backward": _ct_backward_model,
    "input_grad:implicit_gemm": _implicit_gemm_model,
}

_GRID_ORDERS = {
    "filter_grad": ("cin", "cout", "batch", "spatial", "tap"),
    "forward": ("batch", "cout", "cin", "tap"),
    "input_grad": ("batch", "phase", "cin", "cout", "tap"),
    "backward": ("cin", "batch", "phase", "cout", "tap"),
    "ct_backward": ("batch", "cin", "cout", "tap"),
    "input_grad:implicit_gemm": ("batch", "cin", "cout", "tap"),
}


def _model_key(op: str, strategy: str = "phase") -> str:
    """`_MODELS` / `_GRID_ORDERS` key for an (op, strategy) pair.  Phase
    keys are the bare op names (every pre-strategy call site and test
    keeps working); non-phase strategies suffix the op."""
    return op if strategy == "phase" else f"{op}:{strategy}"


def strategy_supported(op: str, strategy: str) -> bool:
    """Whether `strategy` has a kernel family for `op`.  Phase covers
    every op; implicit-GEMM currently covers the standalone input
    gradient only (the fused dual-gradient backward stays
    phase-decomposed), so `plan_strategy` falls back per op."""
    if strategy == "phase":
        return True
    return _model_key(op, strategy) in _MODELS


def _candidates(op: str, g: _Geom, strategy: str, interpret: bool):
    """The candidate (ci_t, co_t, sp_t, u, pu) lattice for one
    (op, strategy) family.  `u` ranges over divisors of the family's
    tap-axis extent: Kh*Kw for the tap-on-grid kernels (including the
    implicit-GEMM flat-tap grid), KP*KQ packed taps per phase for the
    unified phase input gradient -- whose phase axis additionally unrolls
    by `pu` (a divisor of the non-empty phase count).  The filter-grad
    grid spatially tiles; the compiled phase input gradient splits its
    planes into row bands only past `MAX_WINDOW_ROWS_COMPILED`
    (`_band_tile`).  Compiled unrolls cover whole tap
    (and phase) rows, and compiled implicit-GEMM unrolls every tap: its
    windows are slices of an in-register frame, which Mosaic can only
    take at static offsets."""
    kh, kw = g.spec.filter_shape
    t = kh * kw
    ci_cands = _channel_candidates(g.cin, interpret)
    co_cands = _channel_candidates(g.cout, interpret)
    sp_cands = _spatial_candidates(g.oh) if op == "filter_grad" \
        else (g.oh,)
    if op == "input_grad" and strategy == "phase" and not interpret:
        sp_cands = (_band_tile(g),)
    if op in ("input_grad", "backward") and strategy == "phase":
        kp, kq = g.spec.taps_per_phase
        tph, tpw = g.spec.n_tap_phases
        u_cands = _divisors(kp * kq)
        pu_cands = _divisors(tph * tpw)
        w_minor = (kq, tpw)
    elif strategy == "implicit_gemm" and not interpret:
        u_cands = (t,)
        pu_cands = (1,)
        w_minor = (1, 1)
    else:
        u_cands = _divisors(t)
        pu_cands = (1,)
        w_minor = (kw, 1)
    if not interpret:
        # Mosaic reads a window at a traced W (sublane) offset only when
        # it can prove alignment, so compiled steps unroll whole rows of
        # taps / phases: the W offset of every window stays static
        # (`tap_gather.split_index`) and only the H offset is traced.
        u_cands = tuple(v for v in u_cands if v % w_minor[0] == 0)
        pu_cands = tuple(v for v in pu_cands if v % w_minor[1] == 0)
    for ci_t in ci_cands:
        for co_t in co_cands:
            for sp_t in sp_cands:
                for u in u_cands:
                    for pu in pu_cands:
                        yield ci_t, co_t, sp_t, u, pu


def _band_tile(g: _Geom) -> int:
    """dy rows per band of the compiled phase input gradient: the whole
    plane (`g.oh`) when a tap window of it stays within
    `MAX_WINDOW_ROWS_COMPILED` sites, else the largest halving of `g.oh`
    whose band does."""
    _, _, ho, wo, _, _ = _phase_frame(g.spec, g.oh, g.ow)
    for sp in _spatial_candidates(g.oh):
        if phase_bands(g.oh, ho, sp)[0] * wo <= MAX_WINDOW_ROWS_COMPILED:
            return sp
    return 1


def _working_set(op: str, g: _Geom, ci_t, co_t, sp_t, u, pu, interpret,
                 ep=None, strategy: str = "phase") -> int:
    """Modeled VMEM bytes of one candidate; compiled blocks count each
    channel axis at its lane-padded layout width."""
    if not interpret:
        g = dataclasses.replace(g, cin=_lanes(g.cin), cout=_lanes(g.cout))
        ci_t, co_t = _lanes(ci_t), _lanes(co_t)
    return _MODELS[_model_key(op, strategy)](g, ci_t, co_t, sp_t, u, pu,
                                             ep=ep)[0]


def _score(op: str, g: _Geom, ci_t, co_t, sp_t, u, pu, budget, interpret,
           ep=None, strategy: str = "phase"):
    """Modeled cost of one candidate, or None if it violates a constraint."""
    _, traffic, steps, step_blk = _MODELS[_model_key(op, strategy)](
        g, ci_t, co_t, sp_t, u, pu, ep=ep)
    ws = _working_set(op, g, ci_t, co_t, sp_t, u, pu, interpret, ep,
                      strategy)
    if ws > budget:
        return None
    if not interpret and pu * u > MAX_TAP_UNROLL_COMPILED:
        return None   # kernel code size, not VMEM, binds the unroll
    if interpret:
        # The interpret emulation re-materializes every block each step,
        # so its traffic is per-step, not per-index-change.
        traffic = steps * step_blk
        return traffic + steps * STEP_COST_INTERPRET
    return traffic + steps * STEP_COST_COMPILED


def _analytical_best(op: str, spec: ConvSpec, x_shape, dy_shape,
                     itemsize: int, budget: int, interpret: bool,
                     ep: Optional[Epilogue] = None,
                     strategy: str = "phase"):
    """Best candidate for one (op, strategy): (TilePlan, tile cost), with
    cost None when nothing fit and the minimum-footprint fallback was
    taken (the strategy race treats that as a loss)."""
    g = _geom(op, spec, x_shape, dy_shape, itemsize)
    best, best_cost, smallest = None, None, None
    for cand in _candidates(op, g, strategy, interpret):
        ci_t, co_t, sp_t, u, pu = cand
        if interpret or pu * u <= MAX_TAP_UNROLL_COMPILED:
            ws = _working_set(op, g, *cand, interpret, ep, strategy)
            if smallest is None or ws < smallest[0]:
                smallest = (ws, cand)
        cost = _score(op, g, ci_t, co_t, sp_t, u, pu, budget, interpret,
                      ep=ep, strategy=strategy)
        if cost is None:
            continue
        # Deterministic tie-break: prefer larger tiles, then larger unroll
        # (better MXU occupancy at equal modeled cost).
        key = (cost, -ci_t * co_t, -u * pu, -sp_t)
        if best is None or key < best_cost:
            best, best_cost = (ci_t, co_t, sp_t, u, pu), key
    if best is None:
        # Nothing fits the budget: take the candidate with the smallest
        # working set (legal by construction; the kernels' VMEM limit
        # leaves headroom above the budget).  Compiled implicit-GEMM past
        # the unroll cap has no candidate at all and never runs.
        best = smallest[1] if smallest is not None else \
            (min(8, g.cin), min(8, g.cout), 1, 1, 1)
    ci_t, co_t, sp_t, u, pu = best
    plan = TilePlan(cin_tile=ci_t, cout_tile=co_t, spatial_tile=sp_t,
                    tap_unroll=u, phase_unroll=pu,
                    grid_order=_GRID_ORDERS[_model_key(op, strategy)],
                    source="analytical")
    return plan, (None if best_cost is None else best_cost[0])


def _analytical_plan(op: str, spec: ConvSpec, x_shape, dy_shape,
                     itemsize: int, budget: int, interpret: bool,
                     ep: Optional[Epilogue] = None,
                     strategy: str = "phase") -> TilePlan:
    plan, _ = _analytical_best(op, spec, x_shape, dy_shape, itemsize,
                               budget, interpret, ep, strategy)
    return plan


def _strategy_race(op: str, spec: ConvSpec, x_shape, dy_shape,
                   itemsize: int, budget: int, interpret: bool,
                   ep: Optional[Epilogue] = None) -> str:
    """Analytical strategy decision for one geometry: each strategy's
    best tile cost plus what the tile score cannot see --

      * implicit-GEMM pays its predicated lanes: the useful MAC count
        inflated by `1 / (1 - predicated_mac_fraction)` (exact from the
        ConvSpec geometry -- the flat GEMM schedules Fh*Fw rows for
        Oh*Ow useful sites, every tap);
      * phase pays its scheduled taps (ragged-phase padding slots
        included: T * TK >= Kh*Kw) and the host-side residue-interleave
        assembly (ASSEMBLY_PASSES rematerializations of the phase-major
        output tensor, traffic implicit-GEMM never spends).

    Crossover intuition (DESIGN.md Sec. 2.10): high-stride geometries
    (AlexNet S=4/S=8) waste >90% of the flat GEMM's lanes -> phase wins;
    low-stride small-filter geometries (ResNet/ShuffleNet S=2 K=3, any
    S=1 dilated input grad) keep the waste near the 4x floor where the
    flat GEMM's single unpadded residency + zero assembly traffic wins.
    """
    g = _geom(op, spec, x_shape, dy_shape, itemsize)
    kh, kw = spec.filter_shape
    useful = g.b * g.oh * g.ow * kh * kw * g.cin * g.cout
    mac_w = MAC_COST_INTERPRET if interpret else MAC_COST_COMPILED

    _, phase_cost = _analytical_best(op, spec, x_shape, dy_shape, itemsize,
                                     budget, interpret, ep, "phase")
    _, ig_cost = _analytical_best(op, spec, x_shape, dy_shape, itemsize,
                                  budget, interpret, ep, "implicit_gemm")
    if ig_cost is None:
        return "phase"
    if phase_cost is None:
        return "implicit_gemm"

    t, tk, ho, wo, _, _ = _phase_frame(spec, g.oh, g.ow)
    phase_macs = g.b * t * tk * ho * wo * g.cin * g.cout
    assembly = ASSEMBLY_PASSES * g.b * t * ho * wo * g.cin * 4
    waste = ecoflow.predicated_mac_fraction(spec, (g.oh, g.ow))
    ig_macs = useful / max(1e-12, 1.0 - waste)

    phase_total = phase_cost + mac_w * phase_macs + assembly
    ig_total = ig_cost + mac_w * ig_macs
    return "implicit_gemm" if ig_total < phase_total else "phase"


# ---------------------------------------------------------------------------
# Empirical autotune: sweep candidates with the real kernel, cache winners
# ---------------------------------------------------------------------------

# Each kernel module registers `runner(plan) -> seconds` factories here at
# import (keyed by (op, strategy); the strategy defaults to "phase" so
# pre-strategy registrations keep working); tiling itself never imports
# the kernels, so there is no cycle.  A runner factory receives the
# concrete geometry and returns a callable that executes the kernel at
# one candidate plan.
_RUNNERS: Dict[tuple, Callable] = {}


def register_autotune_runner(op: str, factory: Callable,
                             strategy: str = "phase") -> None:
    _RUNNERS[(op, strategy)] = factory


def _median_time_us(fn, iters: int = 5, warmup: int = 2) -> float:
    """Median-of-iters timing, preferring the shared benchmark timer so
    autotune numbers and BENCH_conv.json rows are directly comparable."""
    try:
        from benchmarks.wallclock import _time
        return _time(fn, iters=iters, warmup=warmup)
    except ImportError:
        import statistics
        import time as _t
        fn()
        for _ in range(warmup):
            fn()
        samples = []
        for _ in range(iters):
            t0 = _t.perf_counter()
            fn()
            samples.append(_t.perf_counter() - t0)
        return statistics.median(samples) * 1e6


def cache_path() -> pathlib.Path:
    """`ECOFLOW_TILE_CACHE`, else a fixed file inside the checkout
    (gitignored): a run plans from what the checkout holds, never from a
    stray file in the user's home directory."""
    env = os.environ.get("ECOFLOW_TILE_CACHE")
    if env:
        return pathlib.Path(env)
    return REPO_ROOT / ".ecoflow_cache" / "tile_cache.json"


def _cache_key(op: str, spec: ConvSpec, x_shape, dy_shape, itemsize,
               budget, interpret, ep: Optional[Epilogue] = None,
               strategy: str = "phase") -> str:
    """Execution mode and budget are part of the key: an interpret-tuned
    winner (which may unroll far past MAX_TAP_UNROLL_COMPILED) must never
    be served to a compiled TPU run, and a tightened VMEM budget must
    re-tune rather than replay a plan scored against the old budget.

    The epilogue descriptor is part of the key too (`|ep:<tag>`): an
    epilogue changes the kernel's block set (bias/y/z inputs, the db
    output) and hence which candidates fit and win, so an epilogue-free
    winner must never be replayed for an epilogue-bearing launch.

    So is the strategy (`|st:<strategy>`, including the "auto" race whose
    row records the measured winner): the two strategies' candidate sets
    and kernels differ, so a phase-swept winner must never be replayed
    for an implicit-GEMM launch -- and an `ECOFLOW_STRATEGY` flip must
    re-plan, not serve the stale row.  Rows written before a dimension
    existed carry no suffix for it; `_legacy_cache_keys` reconstructs the
    older key forms and gates which lookups may fall back to them."""
    sh, sw = spec.stride
    ph, pw = spec.padding
    kh, kw = spec.filter_shape
    dh, dw = spec.dilation
    b, nh, nw, cin = x_shape
    _, oh, ow, cout = dy_shape
    mode = "interp" if interpret else "compiled"
    tag = "none" if ep is None else ep.tag
    return (f"{op}|b{b}|n{nh}x{nw}|o{oh}x{ow}|k{kh}x{kw}|s{sh}x{sw}"
            f"|p{ph}x{pw}|d{dh}x{dw}|ci{cin}|co{cout}|w{itemsize}"
            f"|vm{budget}|{mode}|st:{strategy}|ep:{tag}")


def _legacy_cache_keys(key: str) -> tuple:
    """Older key forms of `key`, most recent generation first:

      * pre-strategy rows (`...|ep:<tag>`, no `|st:`) -- swept against
        the phase kernels, so served ONLY for `st:phase` lookups;
      * pre-epilogue rows (no suffix at all) -- additionally gated to
        `ep:none`, whose candidate set they were actually swept against.

    Empty for implicit-GEMM / auto lookups: no legacy sweep ever timed
    those kernels."""
    head, _, tag = key.rpartition("|ep:")
    stem, _, st = head.rpartition("|st:")
    if st != "phase":
        return ()
    legacy = (f"{stem}|ep:{tag}",)
    if tag == "none":
        legacy += (stem,)
    return legacy


_MEM_CACHE: Dict[str, TilePlan] = {}
# Strategy the "auto" autotune race picked, keyed by the |st:auto cache
# key (the TilePlan itself lives in _MEM_CACHE under the same key).
_MEM_STRATEGY: Dict[str, str] = {}


def _load_disk_cache(path: pathlib.Path) -> dict:
    """Read the on-disk autotune cache; {} when absent.

    A file that exists but does not parse as a JSON object (truncated by
    a pre-atomic-write crash, torn by a non-atomic copy, hand-edited) is
    WARNED about and treated as empty -- the sweep re-tunes and the next
    `_store_disk_cache` replaces the file wholesale -- instead of
    crashing the conv that triggered the lookup."""
    try:
        text = path.read_text()
    except OSError:
        return {}
    except UnicodeDecodeError:
        # Exists but is not even text (torn binary copy): same corrupt-
        # cache policy as a JSON parse failure below.
        text, doc = None, None
    if text is not None:
        try:
            doc = json.loads(text)
        except ValueError:
            doc = None
    if not isinstance(doc, dict):
        warnings.warn(
            f"corrupt autotune tile cache at {path} (not a JSON object); "
            f"ignoring it and re-tuning -- the next sweep rewrites it",
            RuntimeWarning, stacklevel=2)
        return {}
    return doc


def _store_disk_cache(path: pathlib.Path, doc: dict) -> None:
    """Atomic publish: write a temp file in the same directory, then
    `os.replace` it over the cache path.  Concurrent autotuning processes
    (multi-device launchers spawn one per host) each publish a COMPLETE
    document -- a racing reader never sees a torn/truncated file, and the
    last writer wins instead of interleaving partial writes."""
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        tmp = path.with_name(f".{path.name}.{os.getpid()}.tmp")
        tmp.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
        os.replace(tmp, path)
    except OSError:
        pass   # cache is an optimization; never fail the conv over it


def _plan_from_cache_rec(op: str, rec: dict) -> Optional[TilePlan]:
    """TilePlan from one cache row, or None (with a warning) when the row
    is malformed -- same warn-and-re-tune policy as a corrupt file."""
    try:
        return TilePlan(cin_tile=rec["cin_tile"],
                        cout_tile=rec["cout_tile"],
                        spatial_tile=rec["spatial_tile"],
                        tap_unroll=rec.get("tap_unroll", 1),
                        phase_unroll=rec.get("phase_unroll", 1),
                        grid_order=tuple(rec.get("grid_order",
                                                 _GRID_ORDERS[op])),
                        source="cache")
    except (KeyError, TypeError, AttributeError):
        warnings.warn(
            f"malformed autotune tile cache record for op {op!r}; "
            f"ignoring it and re-tuning", RuntimeWarning, stacklevel=2)
        return None


def _call_runner_factory(factory: Callable, spec: ConvSpec, x_shape,
                         dy_shape, ep: Optional[Epilogue]):
    """Invoke a runner factory, passing the epilogue only when the factory
    accepts it -- pre-epilogue factories (3-positional signature, still
    used by tests and external registrations) keep working, and an
    epilogue-bearing sweep through such a factory would time the wrong
    kernel, so it is rejected instead of silently mistimed."""
    try:
        accepts_ep = "epilogue" in inspect.signature(factory).parameters
    except (TypeError, ValueError):
        accepts_ep = False
    if accepts_ep:
        return factory(spec, x_shape, dy_shape, epilogue=ep)
    if ep is not None:
        raise TypeError(
            f"autotune runner factory {factory!r} does not accept an "
            f"'epilogue' kwarg but the launch carries epilogue {ep.tag!r}")
    return factory(spec, x_shape, dy_shape)


def _sweep(op: str, spec: ConvSpec, x_shape, dy_shape, itemsize, budget,
           interpret, factory: Callable, ep: Optional[Epilogue],
           strategy: str):
    """Time every feasible candidate of one (op, strategy) through its
    runner: (best TilePlan, best us), or (None, inf) when every candidate
    failed to lower/run."""
    g = _geom(op, spec, x_shape, dy_shape, itemsize)
    run = _call_runner_factory(factory, spec, x_shape, dy_shape, ep)
    best_plan, best_us = None, math.inf
    for ci_t, co_t, sp_t, u, pu in _candidates(op, g, strategy, interpret):
        if _score(op, g, ci_t, co_t, sp_t, u, pu, budget,
                  interpret, ep=ep, strategy=strategy) is None:
            continue
        plan = TilePlan(cin_tile=ci_t, cout_tile=co_t, spatial_tile=sp_t,
                        tap_unroll=u, phase_unroll=pu,
                        grid_order=_GRID_ORDERS[_model_key(op, strategy)],
                        source="autotune")
        try:
            us = _median_time_us(lambda p=plan: run(p))
        except Exception:   # candidate failed to lower/run: skip it
            continue
        if us < best_us:
            best_plan, best_us = plan, us
    return best_plan, best_us


def _autotune_plan(op: str, spec: ConvSpec, x_shape, dy_shape, itemsize,
                   budget, interpret, path: pathlib.Path,
                   runner_factory: Optional[Callable],
                   ep: Optional[Epilogue] = None,
                   strategy: str = "phase") -> TilePlan:
    key = _cache_key(op, spec, x_shape, dy_shape, itemsize, budget,
                     interpret, ep, strategy)
    if key in _MEM_CACHE:
        return _MEM_CACHE[key]
    disk = _load_disk_cache(path)
    if key in disk:
        plan = _plan_from_cache_rec(op, disk[key])
        if plan is not None:
            _MEM_CACHE[key] = plan
            return plan
    for legacy in _legacy_cache_keys(key):
        if legacy in disk:
            # Row written before the strategy / epilogue dimension
            # existed; `_legacy_cache_keys` gates which lookups may be
            # served one (phase-only, and ep:none for the oldest form).
            plan = _plan_from_cache_rec(op, disk[legacy])
            if plan is not None:
                _MEM_CACHE[key] = plan
                return plan
    factory = runner_factory or _RUNNERS.get((op, strategy))
    if factory is None:
        # No runner registered: analytical fallback, through the memo
        # (a distinct mode string so a later call with the runner's
        # module imported still sweeps instead of replaying this plan).
        return _planned(op, spec, x_shape, dy_shape, itemsize, budget,
                        "autotune:analytical-fallback", interpret, ep,
                        strategy)
    best_plan, best_us = _sweep(op, spec, x_shape, dy_shape, itemsize,
                                budget, interpret, factory, ep, strategy)
    if best_plan is None:   # every candidate failed to lower/run
        return _planned(op, spec, x_shape, dy_shape, itemsize, budget,
                        "autotune:analytical-fallback", interpret, ep,
                        strategy)
    disk[key] = dict(best_plan.as_dict(), us=round(best_us, 1),
                     strategy=strategy)
    _store_disk_cache(path, disk)
    _MEM_CACHE[key] = best_plan
    return best_plan


def _autotune_strategy(op: str, spec: ConvSpec, x_shape, dy_shape,
                       itemsize, budget, interpret, path: pathlib.Path,
                       runner_factory: Optional[Callable],
                       ep: Optional[Epilogue]):
    """Empirical strategy race: sweep BOTH strategies' candidate sets
    through their registered runners, return (winning strategy, its best
    TilePlan), and persist ONE row under the `|st:auto` key whose
    `strategy` field records the measured winner.  An explicit
    `runner_factory` stands in for the phase runner only (the
    pre-strategy contract); implicit-GEMM always sweeps through its
    registered runner.  Strategies with no runner are skipped; when none
    has one, the race degrades to the analytical decision."""
    key = _cache_key(op, spec, x_shape, dy_shape, itemsize, budget,
                     interpret, ep, "auto")
    if key in _MEM_CACHE and key in _MEM_STRATEGY:
        return _MEM_STRATEGY[key], _MEM_CACHE[key]
    disk = _load_disk_cache(path)
    if key in disk:
        rec = disk[key]
        plan = _plan_from_cache_rec(op, rec)
        st = rec.get("strategy") if isinstance(rec, dict) else None
        if plan is not None and st in STRATEGIES:
            _MEM_CACHE[key], _MEM_STRATEGY[key] = plan, st
            return st, plan
    best = None   # (us, strategy, plan)
    for strategy in STRATEGIES:
        if not strategy_supported(op, strategy):
            continue
        factory = _RUNNERS.get((op, strategy))
        if factory is None and strategy == "phase":
            factory = runner_factory
        if factory is None:
            continue
        plan, us = _sweep(op, spec, x_shape, dy_shape, itemsize, budget,
                          interpret, factory, ep, strategy)
        if plan is not None and (best is None or us < best[0]):
            best = (us, strategy, plan)
    if best is None:   # no runners at all: analytical race + memoized plan
        strategy = _auto_strategy(op, spec, x_shape, dy_shape, itemsize,
                                  budget, interpret, ep)
        return strategy, _planned(op, spec, x_shape, dy_shape, itemsize,
                                  budget, "autotune:analytical-fallback",
                                  interpret, ep, strategy)
    us, strategy, plan = best
    disk[key] = dict(plan.as_dict(), us=round(us, 1), strategy=strategy)
    _store_disk_cache(path, disk)
    _MEM_CACHE[key], _MEM_STRATEGY[key] = plan, strategy
    return strategy, plan


# ---------------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=4096)
def _planned(op: str, spec: ConvSpec, x_shape, dy_shape, itemsize: int,
             budget: int, mode: str, interpret: bool,
             ep: Optional[Epilogue] = None,
             strategy: str = "phase") -> TilePlan:
    """Memoized analytical resolution.  `kernels/ops.py` re-resolves the
    plan on EVERY conv call (so env flips take effect on the next call,
    not the first trace), which previously re-ran the Python planner each
    time; this memo makes the steady-state cost a dict lookup.  The
    env-derived `budget` and `mode` are part of the key -- resolved by
    `plan_tiles` BEFORE the lookup -- so flipping `ECOFLOW_VMEM_BUDGET`
    or `ECOFLOW_TILING` still re-plans instead of replaying a winner
    scored against stale constraints.  `ep` (a frozen `Epilogue`, or
    None) keys too: the epilogue's extra blocks shift the working set.
    So does `strategy` (resolved from ECOFLOW_STRATEGY before the
    lookup): the strategies' candidate sets and models differ, so a flip
    re-plans instead of serving the other strategy's tiles."""
    return _analytical_plan(op, spec, x_shape, dy_shape, itemsize,
                            budget, interpret, ep, strategy)


@functools.lru_cache(maxsize=4096)
def _auto_strategy(op: str, spec: ConvSpec, x_shape, dy_shape,
                   itemsize: int, budget: int, interpret: bool,
                   ep: Optional[Epilogue] = None) -> str:
    """Memoized analytical strategy race (the `ECOFLOW_STRATEGY=auto`
    default path, resolved per geometry on every conv call)."""
    if not strategy_supported(op, "implicit_gemm"):
        return "phase"
    return _strategy_race(op, spec, x_shape, dy_shape, itemsize, budget,
                          interpret, ep)


def plan_cache_info():
    """Hit/miss statistics of the memoized analytical path (tests and
    benchmarks use this to prove the per-call planner cost is a lookup)."""
    return _planned.cache_info()


def plan_tiles(op: str, spec: ConvSpec, *, x_shape, dy_shape,
               itemsize: int = 4, vmem_budget: Optional[int] = None,
               interpret: bool = False, mode: Optional[str] = None,
               runner_factory: Optional[Callable] = None,
               tile_cache_path=None,
               epilogue: Optional[Epilogue] = None) -> TilePlan:
    """Select (cin_tile, cout_tile, spatial_tile, tap_unroll, grid order)
    for one kernel launch.

    op        -- "filter_grad" | "forward" | "input_grad" | "backward"
                 (fused dual-gradient) | "ct_backward" (fused
                 transposed-conv backward).
    x_shape   -- (B, Nh, Nw, Cin) forward-input shape.
    dy_shape  -- (B, Oh, Ow, Cout) forward-output / error shape.
    itemsize  -- operand dtype bytes (accumulators are always fp32).
    interpret -- True when the kernel will run in interpret mode; weights
                 the per-grid-step cost accordingly.
    mode      -- "analytical" (default) | "autotune"; defaults to the
                 ECOFLOW_TILING env var.
    epilogue  -- the launch's fused `Epilogue` (or None): its bias/y/z
                 blocks and db output enter the working-set model, and
                 its tag enters the autotune cache key (DESIGN.md
                 Sec. 2.8).
    """
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}; expected one of {OPS}")
    x_shape, dy_shape = tuple(map(int, x_shape)), tuple(map(int, dy_shape))
    if epilogue is not None and epilogue.is_identity:
        epilogue = None
    if vmem_budget is None:
        vmem_budget = int(os.environ.get("ECOFLOW_VMEM_BUDGET",
                                         DEFAULT_VMEM_BUDGET))
    if mode is None:
        mode = os.environ.get("ECOFLOW_TILING", "analytical")
    if mode == "autotune":
        path = pathlib.Path(tile_cache_path) if tile_cache_path \
            else cache_path()
        return _autotune_plan(op, spec, x_shape, dy_shape, itemsize,
                              vmem_budget, interpret, path, runner_factory,
                              epilogue)
    return _planned(op, spec, x_shape, dy_shape, itemsize, vmem_budget,
                    mode, interpret, epilogue)


def plan_strategy(op: str, spec: ConvSpec, *, x_shape, dy_shape,
                  itemsize: int = 4, vmem_budget: Optional[int] = None,
                  interpret: bool = False, mode: Optional[str] = None,
                  runner_factory: Optional[Callable] = None,
                  tile_cache_path=None,
                  epilogue: Optional[Epilogue] = None,
                  strategy: Optional[str] = None
                  ) -> tuple[str, TilePlan]:
    """Select the kernel STRATEGY and its tiles for one launch:
    `("phase" | "implicit_gemm", TilePlan)`.

    Same contract and parameters as `plan_tiles` (which this subsumes --
    `plan_tiles` is the strategy-pinned phase view), plus:

    strategy -- "phase" | "implicit_gemm" | "auto" | None.  None reads
                ECOFLOW_STRATEGY (default "auto").  "auto" races the two
                strategies: analytically via the predicated-lane waste
                term against the phase path's scheduled taps + assembly
                traffic (`_strategy_race`), or empirically when
                `mode="autotune"` -- both strategies' candidate sets
                swept through their registered runners, the winner
                persisted with a `strategy` field in its cache row.  A
                forced strategy skips the race but still falls back to
                phase decomposition for ops implicit-GEMM does not
                support (everything except the standalone input
                gradient; the fused dual-gradient backward stays
                phase-decomposed).

    The returned strategy names the kernel family the caller must
    launch; the TilePlan is valid for that family only.  Every cache
    layer (the analytical memo, the in-memory autotune cache, the JSON
    rows) keys on the strategy, so an env flip re-plans.
    """
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}; expected one of {OPS}")
    x_shape, dy_shape = tuple(map(int, x_shape)), tuple(map(int, dy_shape))
    if epilogue is not None and epilogue.is_identity:
        epilogue = None
    if strategy is None:
        strategy = os.environ.get("ECOFLOW_STRATEGY", "auto")
    if strategy not in STRATEGIES + ("auto",):
        raise ValueError(f"unknown strategy {strategy!r} (set explicitly "
                         f"or via ECOFLOW_STRATEGY); expected one of "
                         f"{STRATEGIES + ('auto',)}")
    if strategy != "phase" and not strategy_supported(op, "implicit_gemm"):
        strategy = "phase"   # per-op fallback: no implicit-GEMM kernel
    kh, kw = spec.filter_shape
    if strategy != "phase" and not interpret \
            and kh * kw > MAX_TAP_UNROLL_COMPILED:
        # Compiled implicit-GEMM needs every tap unrolled (static window
        # offsets into its in-register frame); past the cap it has no
        # kernel Mosaic accepts.
        strategy = "phase"
    if vmem_budget is None:
        vmem_budget = int(os.environ.get("ECOFLOW_VMEM_BUDGET",
                                         DEFAULT_VMEM_BUDGET))
    if mode is None:
        mode = os.environ.get("ECOFLOW_TILING", "analytical")
    if mode == "autotune":
        path = pathlib.Path(tile_cache_path) if tile_cache_path \
            else cache_path()
        if strategy == "auto":
            return _autotune_strategy(op, spec, x_shape, dy_shape,
                                      itemsize, vmem_budget, interpret,
                                      path, runner_factory, epilogue)
        return strategy, _autotune_plan(op, spec, x_shape, dy_shape,
                                        itemsize, vmem_budget, interpret,
                                        path, runner_factory, epilogue,
                                        strategy)
    if strategy == "auto":
        strategy = _auto_strategy(op, spec, x_shape, dy_shape, itemsize,
                                  vmem_budget, interpret, epilogue)
    return strategy, _planned(op, spec, x_shape, dy_shape, itemsize,
                              vmem_budget, mode, interpret, epilogue,
                              strategy)


def warmup_plans(entries, *, tile_cache_path=None, itemsize: int = 4,
                 vmem_budget: Optional[int] = None,
                 interpret: bool = False) -> dict:
    """Serving-startup warmup: resolve `(strategy, TilePlan)` for every
    launch a request bucket will make, WITHOUT ever timing a kernel.

    `entries` is an iterable of ``(op, spec, x_shape, dy_shape)`` or
    ``(op, spec, x_shape, dy_shape, epilogue)`` tuples -- the models'
    `*_plan_requests` helpers produce them per bucket.  Resolution order
    per entry, against the shipped `ECOFLOW_TILE_CACHE` artifact at
    `tile_cache_path` (default `cache_path()`):

      1. the artifact's ``|st:auto`` row -- the measured strategy-race
         winner, strategy field and tiles both taken from the row;
      2. the analytical strategy pick, then that strategy's pinned
         artifact row for the tiles if one exists;
      3. the analytical planner (`_planned` memo) otherwise.

    A corrupt artifact (torn file, malformed row) follows the PR 7
    policy -- `RuntimeWarning` and fall through to the analytical path;
    warmup never fails engine startup and never runs an autotune sweep.
    Artifact hits are primed into the in-memory autotune caches, so a
    serve process running `ECOFLOW_TILING=autotune` replays the shipped
    rows instead of sweeping on the first request.

    Returns ``{cache_key: {"op", "strategy", "plan", "source"}}`` with
    ``source`` in ``{"artifact", "analytical"}``.
    """
    if vmem_budget is None:
        vmem_budget = int(os.environ.get("ECOFLOW_VMEM_BUDGET",
                                         DEFAULT_VMEM_BUDGET))
    path = pathlib.Path(tile_cache_path) if tile_cache_path \
        else cache_path()
    disk = _load_disk_cache(path)   # corrupt artifact -> warn + {}
    out = {}
    for entry in entries:
        op, spec, x_shape, dy_shape = entry[:4]
        ep = entry[4] if len(entry) > 4 else None
        if op not in OPS:
            raise ValueError(f"unknown op {op!r}; expected one of {OPS}")
        if ep is not None and ep.is_identity:
            ep = None
        x_shape = tuple(map(int, x_shape))
        dy_shape = tuple(map(int, dy_shape))

        strategy = plan = None
        source = "artifact"
        key_auto = _cache_key(op, spec, x_shape, dy_shape, itemsize,
                              vmem_budget, interpret, ep, "auto")
        rec = disk.get(key_auto)
        if isinstance(rec, dict):
            p = _plan_from_cache_rec(op, rec)   # warns on a torn row
            st = rec.get("strategy")
            if p is not None and st in STRATEGIES:
                strategy, plan = st, p
                _MEM_CACHE[key_auto] = plan
                _MEM_STRATEGY[key_auto] = strategy
        if plan is None:
            strategy = _auto_strategy(op, spec, x_shape, dy_shape,
                                      itemsize, vmem_budget, interpret, ep)
            key_st = _cache_key(op, spec, x_shape, dy_shape, itemsize,
                                vmem_budget, interpret, ep, strategy)
            rec = disk.get(key_st)
            if isinstance(rec, dict):
                plan = _plan_from_cache_rec(op, rec)
            if plan is not None:
                _MEM_CACHE[key_st] = plan
            else:
                plan = _planned(op, spec, x_shape, dy_shape, itemsize,
                                vmem_budget, "analytical", interpret, ep,
                                strategy)
                source = "analytical"
        out[key_auto] = {"op": op, "strategy": strategy, "plan": plan,
                         "source": source}
    return out
