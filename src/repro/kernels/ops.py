"""Public wrappers around the Pallas kernels.

`tconv_phase` is the fused zero-free transposed convolution -- ONE
`pallas_call` computes the input gradient of any (stride, dilation)
forward conv via the unified (phase, tap) grid; `dconv_filter_grad` is
the zero-free filter gradient with in-kernel tap gathering (no K^2 input
replication, dilation-aware tap offsets); `dconv_forward` is the fused
zero-free dilated (atrous) forward conv with the dilation taps on the
grid; `conv_backward` / `tconv_backward` are the fused DUAL-GRADIENT
backwards -- both gradients of a conv VJP from one launch sharing a
single fetch of the common operand (dy for the direct conv, the
cotangent for the transposed conv).  All run the kernels in interpret
mode on CPU (the container target) and compiled mode on real TPUs.
These are the `pallas` conv backend
(`repro.core.spec.resolve_backend("pallas")`).

The interpret/compiled decision is resolved PER CALL, not at import: an
import-time `jax.default_backend()` both forces backend initialization as
a side effect of importing this module and goes stale if the device set
changes afterwards (e.g. a TPU runtime initialized late, or tests that
swap platforms).  The kernel entry points are themselves jit'd with
`interpret` static, so each resolved value gets its own compiled cache
entry and nothing re-traces per call.

Tiling is geometry-aware: these wrappers resolve the TilePlan
(cin/cout/spatial tiles, tap/phase unroll) through
`repro.kernels.tiling.plan_tiles` ON EVERY CALL -- from the ConvSpec,
operand shapes, dtype, and the VMEM budget -- and pass it to the kernels
as explicit static arguments.  Resolving OUTSIDE the jit'd kernels
matters: a plan change (flipping `ECOFLOW_TILING=autotune`, a refreshed
tile cache, a new `ECOFLOW_VMEM_BUDGET`) re-keys the kernel's compile
cache and takes effect on the next call, instead of being frozen into
the first trace the way a kernel-internal default would be (kernels
called directly with tile arguments left as None plan at trace time and
carry that caveat).  Analytical model by default; see DESIGN.md
Sec. 2.6.
"""
from __future__ import annotations

import jax

from repro.core.spec import ConvSpec, _pair
from repro.kernels import tiling
from repro.kernels.attention import flash_attention_pallas
from repro.kernels.dconv_backward import (conv_backward_pallas,
                                          tconv_backward_pallas)
from repro.kernels.dconv_filtergrad import dconv_filter_grad_pallas
from repro.kernels.dconv_forward import dconv_forward_pallas
from repro.kernels.implicit_gemm import tconv_implicit_gemm_pallas
from repro.kernels.tconv_phase import tconv_fused_pallas


def interpret_mode() -> bool:
    """True off-TPU (run the kernels in interpret mode), resolved lazily
    at call time -- see the module docstring.  The ONE place the repo
    decides between interpret and compiled Pallas: every wrapper here,
    the autotune runners and the serving engine ask it, and the kernels
    themselves take `interpret` as a required argument, so patching this
    function steers a whole traced step."""
    return jax.default_backend() != "tpu"


def flash_attention(q, k, v, *, causal=True, blk_q=128, blk_k=128):
    """Blockwise causal GQA attention via the Pallas flash kernel."""
    return flash_attention_pallas(q, k, v, causal=causal, blk_q=blk_q,
                                  blk_k=blk_k, interpret=interpret_mode())


def tconv_phase(dy: jax.Array, w: jax.Array, *, stride, padding,
                n_out, dilation=(1, 1), bias=None,
                epilogue=None, strategy=None) -> jax.Array:
    """Fused zero-free transposed conv / input gradient: ONE Pallas
    launch for any (stride, dilation) geometry, through the strategy
    planner -- `tiling.plan_strategy` races the phase decomposition
    against the predicated implicit-GEMM kernel per geometry and this
    wrapper launches whichever family the plan names (both preserve the
    one-launch invariant and the epilogue contract).

    dy (B,Oh,Ow,Cout), w (Kh,Kw,Cin,Cout) -> dx (B,Nh,Nw,Cin).
    `epilogue` / `bias` fuse act(scale * . + bias) onto the output
    in-kernel (bias over the OUTPUT channels Cin).
    `strategy` pins "phase" | "implicit_gemm" | "auto" for this call
    (None reads ECOFLOW_STRATEGY; benchmarks use the pin to time one
    strategy without env juggling).
    """
    spec = ConvSpec.make(stride=stride, padding=padding,
                         filter_shape=(w.shape[0], w.shape[1]),
                         dilation=dilation)
    nh, nw = _pair(n_out)
    strategy, plan = tiling.plan_strategy(
        "input_grad", spec, x_shape=(dy.shape[0], nh, nw, w.shape[2]),
        dy_shape=dy.shape, itemsize=dy.dtype.itemsize,
        interpret=interpret_mode(), epilogue=epilogue, strategy=strategy)
    if strategy == "implicit_gemm":
        return tconv_implicit_gemm_pallas(
            dy, w, stride=tuple(stride), padding=tuple(padding),
            n_out=(nh, nw), dilation=tuple(dilation),
            bias=bias, epilogue=epilogue,
            cin_tile=plan.cin_tile, cout_tile=plan.cout_tile,
            tap_unroll=plan.tap_unroll, interpret=interpret_mode())
    return tconv_fused_pallas(dy, w, stride=tuple(stride),
                              padding=tuple(padding), n_out=(nh, nw),
                              dilation=tuple(dilation),
                              bias=bias, epilogue=epilogue,
                              cin_tile=plan.cin_tile,
                              cout_tile=plan.cout_tile,
                              tap_unroll=plan.tap_unroll,
                              phase_unroll=plan.phase_unroll,
                              interpret=interpret_mode())


def dconv_filter_grad(x: jax.Array, dy: jax.Array, *, stride, padding,
                      k, dilation=(1, 1)) -> jax.Array:
    """Zero-free filter gradient via the in-kernel tap-gather matmul."""
    spec = ConvSpec.make(stride=stride, padding=padding, filter_shape=k,
                         dilation=dilation)
    plan = tiling.plan_tiles("filter_grad", spec, x_shape=x.shape,
                             dy_shape=dy.shape, itemsize=x.dtype.itemsize,
                             interpret=interpret_mode())
    return dconv_filter_grad_pallas(x, dy, stride=tuple(stride),
                                    padding=tuple(padding), k=tuple(k),
                                    dilation=tuple(dilation),
                                    cin_tile=plan.cin_tile,
                                    cout_tile=plan.cout_tile,
                                    spatial_tile=plan.spatial_tile,
                                    tap_unroll=plan.tap_unroll,
                                    interpret=interpret_mode())


def conv_backward(x: jax.Array, dy: jax.Array, w: jax.Array, *, stride,
                  padding, n_out, dilation=(1, 1), y=None, epilogue=None):
    """Fused dual-gradient conv backward: (dx, dW) from ONE Pallas
    launch sharing a single dy fetch (kernels/dconv_backward.py).

    x (B,Nh,Nw,Cin), dy (B,Oh,Ow,Cout), w (Kh,Kw,Cin,Cout)
    -> (dx (B,Nh,Nw,Cin), dW (Kh,Kw,Cin,Cout)).
    With `epilogue` this is the VJP of the epilogue-fused forward (`y` is
    its output residual): the act'(y) mask is applied in-VMEM and the
    return gains the in-kernel bias gradient, (dx, dW, db|None).
    """
    spec = ConvSpec.make(stride=stride, padding=padding,
                         filter_shape=(w.shape[0], w.shape[1]),
                         dilation=dilation)
    nh, nw = _pair(n_out)
    plan = tiling.plan_tiles("backward", spec, x_shape=x.shape,
                             dy_shape=dy.shape,
                             itemsize=dy.dtype.itemsize,
                             interpret=interpret_mode(), epilogue=epilogue)
    return conv_backward_pallas(x, dy, w, stride=spec.stride,
                                padding=spec.padding, n_out=(nh, nw),
                                dilation=spec.dilation,
                                y=y, epilogue=epilogue,
                                cin_tile=plan.cin_tile,
                                cout_tile=plan.cout_tile,
                                tap_unroll=plan.tap_unroll,
                                phase_unroll=plan.phase_unroll,
                                interpret=interpret_mode())


def tconv_backward(g: jax.Array, dy: jax.Array, w: jax.Array, *, stride,
                   padding, dilation=(1, 1), z=None, epilogue=None):
    """Fused transposed-conv backward: (ddy, dW) from ONE Pallas launch
    sharing a single cotangent fetch (every tap gather feeds both the
    conv matmul and the filter-grad matmul).

    g (B,Nh,Nw,Cin) cotangent, dy (B,Oh,Ow,Cout), w (Kh,Kw,Cin,Cout)
    -> (ddy (B,Oh,Ow,Cout), dW (Kh,Kw,Cin,Cout)).
    With `epilogue` this is the VJP of the epilogue-fused transposed conv
    (`z` is its output residual) and returns (ddy, dW, db|None).
    """
    spec = ConvSpec.make(stride=stride, padding=padding,
                         filter_shape=(w.shape[0], w.shape[1]),
                         dilation=dilation)
    plan = tiling.plan_tiles("ct_backward", spec, x_shape=g.shape,
                             dy_shape=dy.shape,
                             itemsize=g.dtype.itemsize,
                             interpret=interpret_mode(), epilogue=epilogue)
    return tconv_backward_pallas(g, dy, w, stride=spec.stride,
                                 padding=spec.padding,
                                 dilation=spec.dilation,
                                 z=z, epilogue=epilogue,
                                 cin_tile=plan.cin_tile,
                                 cout_tile=plan.cout_tile,
                                 tap_unroll=plan.tap_unroll,
                                 interpret=interpret_mode())


def dconv_forward(x: jax.Array, w: jax.Array, *, stride, padding,
                  dilation, bias=None, epilogue=None) -> jax.Array:
    """Fused zero-free dilated (atrous) forward conv: one Pallas launch
    with the dilation taps on the grid.

    x (B,Nh,Nw,Cin), w (Kh,Kw,Cin,Cout) -> y (B,Oh,Ow,Cout).
    `epilogue` / `bias` fuse act(scale * conv + bias) onto the resident
    output block before its HBM store.
    """
    spec = ConvSpec.make(stride=stride, padding=padding,
                         filter_shape=(w.shape[0], w.shape[1]),
                         dilation=dilation)
    oh, ow = spec.out_size((x.shape[1], x.shape[2]))
    if oh < 1 or ow < 1:
        # Degenerate geometry: skip planning, let the kernel raise its
        # too-small-input ValueError with the full context.
        return dconv_forward_pallas(x, w, stride=tuple(stride),
                                    padding=tuple(padding),
                                    dilation=tuple(dilation),
                                    interpret=interpret_mode())
    plan = tiling.plan_tiles("forward", spec, x_shape=x.shape,
                             dy_shape=(x.shape[0], oh, ow, w.shape[3]),
                             itemsize=x.dtype.itemsize,
                             interpret=interpret_mode(), epilogue=epilogue)
    return dconv_forward_pallas(x, w, stride=tuple(stride),
                                padding=tuple(padding),
                                dilation=tuple(dilation),
                                bias=bias, epilogue=epilogue,
                                cin_tile=plan.cin_tile,
                                cout_tile=plan.cout_tile,
                                tap_unroll=plan.tap_unroll,
                                interpret=interpret_mode())
