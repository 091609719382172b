"""ConvSpec: normalized convolution geometry + the conv backend registry.

Every convolution in the repo (forward -- plain or dilated/atrous,
zero-free input-gradient / transposed, zero-free filter-gradient /
dilated) is described by one `ConvSpec` -- stride/padding/filter/dilation
pairs plus the derived phase bookkeeping the EcoFlow decomposition needs
(sub-filter shapes, effective receptive field, full/output sizes).  This absorbs the `_pair` / `transposed_conv_input_size` helpers
previously duplicated across `core/ecoflow.py` and `kernels/ops.py`.

Backends implement the three ops behind a uniform interface and register
under a name:

  * ``reference``      -- `jax.vjp` of `lax.conv_general_dilated`
                          (ground truth; materializes dilation zeros).
  * ``xla_zero_free``  -- the EcoFlow phase decomposition expressed as
                          dense XLA ops (S*S stride-1 convs + scatters,
                          per-tap strided gathers).  This is the
                          multi-launch path the fused kernels replace; it
                          is kept as a backend both as a fallback and as
                          the baseline the benchmarks compare against.
  * ``pallas``         -- the fused single-launch Pallas TPU kernels
                          (`kernels/tconv_phase.py`,
                          `kernels/dconv_filtergrad.py`, and the
                          predicated `kernels/implicit_gemm.py` the
                          strategy planner races against the phase
                          decomposition per geometry); interpret mode
                          off-TPU.  Tile extents are NOT pinned here:
                          every kernel resolves its tiling per geometry
                          through `kernels/tiling.py` (the old
                          `tile: int = 128` defaults are gone).

`resolve_backend` also accepts the legacy `use_pallas` booleans
(False -> xla_zero_free, True -> pallas) so old call sites keep working.

See DESIGN.md Sec. 2 for the EcoFlow -> MXU mapping the backends realize.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, Optional, Sequence, Tuple, Union

# A backend designator: None (default), legacy use_pallas bool, a name, a
# ConvBackend, or a SEQUENCE of designators -- the last resolves through
# `fallback_backend` into a graceful-degradation ladder that tries each
# entry in order (DESIGN.md Sec. 2.11).
BackendLike = Union[None, bool, str, "ConvBackend",
                    Sequence[Union[None, bool, str, "ConvBackend"]]]

DEFAULT_BACKEND = "xla_zero_free"

_ACTIVATIONS = ("none", "relu", "leaky_relu", "tanh")


@dataclasses.dataclass(frozen=True)
class Epilogue:
    """Elementwise tail fused into a conv launch (DESIGN.md Sec. 2.8).

    Describes y = act(scale * conv + bias): an optional scalar scale, an
    optional per-output-channel bias add, then one of the supported
    activations.  The descriptor is frozen/hashable so it can ride through
    `jax.jit` static arguments and `jax.custom_vjp` nondiff argnums; the
    bias VECTOR itself stays a traced operand (an extra kernel input).

    The backward contract exploits that every supported activation's
    derivative is recoverable from the activation OUTPUT y (no
    pre-activation residual needed): relu' = (y > 0), leaky_relu' =
    where(y > 0, 1, slope) for slope > 0, tanh' = 1 - y^2.  `grad_factor`
    is that derivative; the fused backward kernels apply it in-VMEM to the
    resident cotangent block before the dx/dW matmuls and accumulate the
    bias gradient (sum of the masked cotangent) as a third kernel output.
    """
    activation: str = "none"
    bias: bool = False
    slope: float = 0.01           # leaky_relu negative slope (> 0)
    scale: Optional[float] = None  # scalar multiplier on the conv output

    def __post_init__(self):
        if self.activation not in _ACTIVATIONS:
            raise ValueError(f"unknown epilogue activation "
                             f"{self.activation!r}; expected one of "
                             f"{_ACTIVATIONS}")
        if self.activation == "leaky_relu" and not self.slope > 0:
            # slope 0 would be plain relu; slope < 0 breaks the
            # y-recoverable-derivative contract (sign(y) != sign(pre)).
            raise ValueError(f"leaky_relu slope must be > 0, "
                             f"got {self.slope}")

    @property
    def is_identity(self) -> bool:
        return (self.activation == "none" and not self.bias
                and self.scale is None)

    @property
    def needs_y(self) -> bool:
        """True when the backward needs the forward output residual (the
        activation-gradient mask is a function of y)."""
        return self.activation != "none"

    @property
    def tag(self) -> str:
        """Compact stable string for cache keys / bench rows."""
        if self.is_identity:
            return "none"
        act = self.activation
        if act == "leaky_relu":
            act += f"{self.slope:g}"
        parts = (["b"] if self.bias else []) \
            + ([act] if act != "none" else [])
        if self.scale is not None:
            parts.append(f"s{self.scale:g}")
        return "+".join(parts)

    def apply(self, vals, bias=None):
        """Forward tail: act(scale * vals + bias).  Pure jnp elementwise,
        usable both host-side (reference/xla backends) and on a
        VMEM-resident block inside a Pallas kernel."""
        import jax.numpy as jnp
        if self.bias and bias is None:
            raise ValueError("epilogue requests a bias but none was given")
        if self.scale is not None:
            vals = vals * self.scale
        if bias is not None:
            vals = vals + bias.astype(vals.dtype)
        if self.activation == "relu":
            vals = jnp.maximum(vals, 0.0)
        elif self.activation == "leaky_relu":
            vals = jnp.where(vals > 0, vals, self.slope * vals)
        elif self.activation == "tanh":
            vals = jnp.tanh(vals)
        return vals

    def grad_factor(self, y):
        """Activation derivative act'(pre), computed from the OUTPUT y."""
        import jax.numpy as jnp
        if self.activation == "relu":
            return (y > 0).astype(y.dtype)
        if self.activation == "leaky_relu":
            return jnp.where(y > 0, 1.0, self.slope).astype(y.dtype)
        if self.activation == "tanh":
            return 1.0 - jnp.square(y)
        return None

    def mask_cotangent(self, y, g):
        """g * act'(y): the masked (UNSCALED) cotangent.  The bias
        gradient is its channel-wise sum; dx/dW additionally carry the
        scalar `scale` factor."""
        f = self.grad_factor(y)
        return g if f is None else g * f.astype(g.dtype)


def _pair(v) -> tuple[int, int]:
    """Normalize an int-or-2-sequence to an (int, int) tuple."""
    if isinstance(v, (tuple, list)):
        if len(v) != 2:
            raise ValueError(f"expected 2 elements, got {v!r}")
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """Static geometry of one convolution (NHWC x HWIO).

    All fields are per-axis (h, w) pairs; construct with `ConvSpec.make`
    to get int -> pair normalization.  The spec is hashable, so it can be
    a static argument of jit'd functions.
    """
    stride: tuple[int, int] = (1, 1)
    padding: tuple[int, int] = (0, 0)
    filter_shape: tuple[int, int] = (1, 1)   # (Kh, Kw)
    dilation: tuple[int, int] = (1, 1)       # forward filter dilation

    @classmethod
    def make(cls, *, stride=1, padding=0, filter_shape=1,
             dilation=1) -> "ConvSpec":
        """Validated constructor.  Rejects degenerate geometry with
        `ValueError` (NOT `assert`, which `python -O` strips): a stride of
        0 otherwise surfaces as a `ZeroDivisionError` deep inside the
        phase bookkeeping, and negative padding as silent wrong shapes."""
        stride = _pair(stride)
        padding = _pair(padding)
        filter_shape = _pair(filter_shape)
        dilation = _pair(dilation)
        if min(stride) < 1:
            raise ValueError(f"stride must be >= 1, got {stride}")
        if min(padding) < 0:
            raise ValueError(f"padding must be >= 0, got {padding}")
        if min(filter_shape) < 1:
            raise ValueError(f"filter_shape must be >= 1, got {filter_shape}")
        if min(dilation) < 1:
            raise ValueError(f"dilation must be >= 1, got {dilation}")
        return cls(stride, padding, filter_shape, dilation)

    # -- forward geometry ---------------------------------------------------

    @property
    def dilated_filter_shape(self) -> tuple[int, int]:
        """Effective receptive field K_eff = D*(K-1) + 1 per axis: the
        spatial extent of the filter once its taps are spread D apart.
        Equals `filter_shape` at dilation 1."""
        return tuple(self.dilation[i] * (self.filter_shape[i] - 1) + 1
                     for i in range(2))

    def out_size(self, in_size: Sequence[int]) -> tuple[int, int]:
        """Forward output spatial size O = floor((N + 2P - K_eff)/S) + 1."""
        n = _pair(in_size)
        ke = self.dilated_filter_shape
        return tuple((n[i] + 2 * self.padding[i] - ke[i])
                     // self.stride[i] + 1 for i in range(2))

    def input_size(self, out_size: Sequence[int]) -> tuple[int, int]:
        """Exact-fit forward input size N = S*(O-1) + K_eff - 2P (the
        default `n_out` of the transposed conv)."""
        o = _pair(out_size)
        ke = self.dilated_filter_shape
        return tuple(self.stride[i] * (o[i] - 1) + ke[i]
                     - 2 * self.padding[i] for i in range(2))

    def full_size(self, out_size: Sequence[int]) -> tuple[int, int]:
        """Pre-padding-slice transposed-conv output size F = S*(O-1) +
        K_eff."""
        o = _pair(out_size)
        ke = self.dilated_filter_shape
        return tuple(self.stride[i] * (o[i] - 1) + ke[i]
                     for i in range(2))

    # -- phase (EcoFlow) bookkeeping ----------------------------------------
    # The stride-phase properties below (n_phases .. useful_taps) describe
    # the transposed conv of an UNDILATED forward conv (dilation 1).  The
    # stride x dilation GENERAL decomposition -- tap (kx, ky) lands in
    # output residue class ((kx*D) mod S, (ky*D) mod S), taps group by
    # kx mod (S/gcd(S, D)), and within a residue class successive taps sit
    # D/gcd(S, D) phase rows apart -- is the tap_* family at the end of
    # this block (see DESIGN.md Sec. 2.5).  At dilation 1 the two views
    # coincide (period == stride, step == 1).

    @property
    def n_phases(self) -> int:
        """Number of stride phases S_h * S_w of the transposed conv."""
        return self.stride[0] * self.stride[1]

    def phase_index(self, p: int, q: int) -> int:
        """Linear index of phase (p, q) in the packed phase-major layout."""
        return p * self.stride[1] + q

    def phase_filter_shape(self, p: int, q: int) -> tuple[int, int]:
        """Sub-filter taps of phase (p, q): ceil((K - p)/S) per axis.
        Zero for phases beyond the filter extent (stride > K)."""
        return (max(0, -(-(self.filter_shape[0] - p) // self.stride[0])),
                max(0, -(-(self.filter_shape[1] - q) // self.stride[1])))

    @property
    def packed_phase_shape(self) -> tuple[int, int]:
        """Uniform (zero-padded) sub-filter shape ceil(K/S) per axis --
        the tap extent of the packed all-phase filter tensor."""
        return (-(-self.filter_shape[0] // self.stride[0]),
                -(-self.filter_shape[1] // self.stride[1]))

    def useful_taps(self) -> int:
        """Total taps over all phases == Kh*Kw (every tap in exactly one
        phase; the zero-free property)."""
        return sum(kp * kq
                   for p in range(self.stride[0])
                   for q in range(self.stride[1])
                   for kp, kq in [self.phase_filter_shape(p, q)])

    # -- stride x dilation general (tap-phase) bookkeeping -------------------
    # Transposed conv of a forward conv with stride S and filter dilation D:
    # tap kx contributes to full-output rows r = i*S + kx*D, i.e. residue
    # class (kx*D) mod S.  Residues repeat with period S/gcd(S, D) in kx, so
    # taps group by kx mod period, and taps kx = a + u*period of class `a`
    # land on phase rows m = i + (a*D)//S + u*(D/gcd(S, D)) -- an arithmetic
    # tap lattice: each residue class is a stride-1 correlation of dy with a
    # (D/gcd)-dilated sub-filter.  At D == 1 this reduces exactly to the
    # stride-phase properties above.

    @property
    def tap_phase_period(self) -> tuple[int, int]:
        """Tap-grouping period S/gcd(S, D) per axis: taps kx and
        kx + period share the output residue class (kx*D) mod S."""
        return tuple(self.stride[i] // math.gcd(self.stride[i],
                                                self.dilation[i])
                     for i in range(2))

    @property
    def tap_phase_step(self) -> tuple[int, int]:
        """Phase-row spacing D/gcd(S, D) between successive taps of one
        residue class (the sub-filter's own dilation rate)."""
        return tuple(self.dilation[i] // math.gcd(self.stride[i],
                                                  self.dilation[i])
                     for i in range(2))

    @property
    def n_tap_phases(self) -> tuple[int, int]:
        """Non-empty residue classes min(K, period) per axis; the remaining
        stride residues receive no tap (structural zeros of the
        upsampling)."""
        per = self.tap_phase_period
        return tuple(min(self.filter_shape[i], per[i]) for i in range(2))

    @property
    def taps_per_phase(self) -> tuple[int, int]:
        """Uniform (zero-padded) within-phase tap count ceil(K/period) per
        axis -- the packed tap extent of the general decomposition."""
        per = self.tap_phase_period
        return tuple(-(-self.filter_shape[i] // per[i]) for i in range(2))

    def tap_phase_residue(self, a: int, axis: int) -> int:
        """Output residue class (a*D) mod S of tap-phase `a` on `axis`."""
        return (a * self.dilation[axis]) % self.stride[axis]

    def tap_phase_base(self, a: int, axis: int) -> int:
        """Leading phase-row offset (a*D) // S of tap-phase `a`: the row
        where that class's first tap (u = 0) lands for output i = 0."""
        return (a * self.dilation[axis]) // self.stride[axis]


# ---------------------------------------------------------------------------
# Backend registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ConvBackend:
    """One implementation of the conv ops.

    forward(x, w, spec)                -> y     (B,N,N,Cin)x(K,K,Cin,Cout)
    input_grad(dy, w, spec, n_out)     -> dx    zero-free transposed conv
    filter_grad(x, dy, spec)           -> dw    zero-free dilated conv

    All three honor `spec.dilation` (forward filter dilation): the forward
    op is then a dilated/atrous conv and the gradients are its adjoints.

    A backend may additionally provide FUSED backward implementations
    (`fused_backward` / `fused_ct_backward`): both gradients of a conv's
    VJP from a single kernel launch sharing one fetch of the common
    operand (see kernels/dconv_backward.py, DESIGN.md Sec. 2.7).  The
    `backward` / `ct_backward` methods below are what `core/conv.py`
    dispatches through: they use the fused path when the backend has one
    and otherwise fall back to the equivalent two-launch composition of
    the primitive ops -- so `reference` and `xla_zero_free` (and any
    externally registered three-op backend) keep working unchanged.
    """
    name: str
    forward: Callable
    input_grad: Callable
    filter_grad: Callable
    # (x, dy, w, spec, n_out) -> (dx, dw): direct-conv VJP, shared dy.
    fused_backward: Union[Callable, None] = None
    # (g, dy, w, spec) -> (ddy, dw): transposed-conv VJP, shared g.
    fused_ct_backward: Union[Callable, None] = None
    # Epilogue-fused variants (DESIGN.md Sec. 2.8).  When absent, the
    # generic *_ep methods compose the plain ops with Epilogue.apply /
    # Epilogue.mask_cotangent -- mathematically identical, so the parity
    # grids hold across backends with or without fused implementations.
    # (x, w, bias, spec, ep) -> y
    fused_forward_ep: Union[Callable, None] = None
    # (dy, w, bias, spec, n_out, ep) -> x
    fused_input_grad_ep: Union[Callable, None] = None
    # (x, y, dy, w, spec, n_out, ep) -> (dx, dw, db|None)
    fused_backward_ep: Union[Callable, None] = None
    # (g, z, dy, w, spec, ep) -> (ddy, dw, db|None)
    fused_ct_backward_ep: Union[Callable, None] = None

    def backward(self, x, dy, w, spec: "ConvSpec", n_out):
        """Both gradients of direct_conv(x, w, spec) w.r.t. cotangent dy:
        (dx, dw).  One launch on backends with a fused kernel; the
        two-launch input_grad + filter_grad composition otherwise."""
        if self.fused_backward is not None:
            return self.fused_backward(x, dy, w, spec, n_out)
        dx = self.input_grad(dy, w, spec, n_out)
        dw = self.filter_grad(x, dy, spec)
        return dx, dw

    def ct_backward(self, g, dy, w, spec: "ConvSpec"):
        """Both gradients of the transposed conv tconv(dy, w, spec)
        w.r.t. cotangent g: (ddy, dw).  The adjoint pair is (direct conv
        of g, filter grad with g in the input role) -- the shared operand
        is g, so the fused kernel shares its fetch (and tap gathers)."""
        if self.fused_ct_backward is not None:
            return self.fused_ct_backward(g, dy, w, spec)
        ddy = self.forward(g, w, spec)
        dw = self.filter_grad(g, dy, spec)
        return ddy, dw

    # -- epilogue-fused entry points (DESIGN.md Sec. 2.8) ------------------

    def forward_ep(self, x, w, bias, spec: "ConvSpec", ep: Epilogue):
        """y = ep.apply(forward(x, w), bias), fused in-kernel when the
        backend has an epilogue slot."""
        if self.fused_forward_ep is not None:
            return self.fused_forward_ep(x, w, bias, spec, ep)
        return ep.apply(self.forward(x, w, spec), bias)

    def input_grad_ep(self, dy, w, bias, spec: "ConvSpec", n_out,
                      ep: Epilogue):
        """Transposed conv with a fused tail: the generator-style
        tconv-as-a-layer use, NOT the conv adjoint."""
        if self.fused_input_grad_ep is not None:
            return self.fused_input_grad_ep(dy, w, bias, spec, n_out, ep)
        return ep.apply(self.input_grad(dy, w, spec, n_out), bias)

    def backward_ep(self, x, y, dy, w, spec: "ConvSpec", n_out,
                    ep: Epilogue):
        """VJP of forward_ep: masks the cotangent with act'(y), then the
        shared dx/dW launch; db (sum of the masked cotangent) rides along
        as a third output when ep.bias.  Returns (dx, dw, db|None)."""
        if self.fused_backward_ep is not None:
            return self.fused_backward_ep(x, y, dy, w, spec, n_out, ep)
        m = ep.mask_cotangent(y, dy)
        db = m.sum(axis=(0, 1, 2)) if ep.bias else None
        if ep.scale is not None:
            m = m * ep.scale
        dx, dw = self.backward(x, m, w, spec, n_out)
        return dx, dw, db

    def ct_backward_ep(self, g, z, dy, w, spec: "ConvSpec", ep: Epilogue):
        """VJP of input_grad_ep (z is its forward output).  Returns
        (ddy, dw, db|None)."""
        if self.fused_ct_backward_ep is not None:
            return self.fused_ct_backward_ep(g, z, dy, w, spec, ep)
        m = ep.mask_cotangent(z, g)
        db = m.sum(axis=(0, 1, 2)) if ep.bias else None
        if ep.scale is not None:
            m = m * ep.scale
        ddy, dw = self.ct_backward(m, dy, w, spec)
        return ddy, dw, db


_BACKENDS: Dict[str, ConvBackend] = {}


def register_backend(backend: ConvBackend) -> ConvBackend:
    _BACKENDS[backend.name] = backend
    return backend


def available_backends() -> tuple[str, ...]:
    _ensure_default_backends()
    return tuple(sorted(_BACKENDS))


def resolve_backend(backend: BackendLike) -> ConvBackend:
    """Name / bool / None / ConvBackend / sequence-of-those -> ConvBackend.

    A tuple or list resolves through `fallback_backend`: a degradation
    ladder trying each entry in order.  Tuples of names stay hashable, so
    a ladder can ride through `jax.jit` static arguments and
    `jax.custom_vjp` nondiff argnums exactly like a plain name."""
    _ensure_default_backends()
    if isinstance(backend, ConvBackend):
        return backend
    if isinstance(backend, (tuple, list)):
        return fallback_backend(tuple(backend))
    if backend is None:
        name = DEFAULT_BACKEND
    elif isinstance(backend, bool):  # legacy use_pallas flag
        name = "pallas" if backend else "xla_zero_free"
    else:
        name = str(backend)
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown conv backend {name!r}; available: "
            f"{', '.join(available_backends())}") from None


# ---------------------------------------------------------------------------
# Graceful degradation: a fallback ladder over backends (DESIGN.md
# Sec. 2.11).  `ConvServeEngine` drives its per-bucket ladder explicitly
# (it needs circuit-breaker state around each rung); this seam is the
# same semantics for every OTHER call site -- pass a tuple of backend
# names anywhere a backend goes and a failing fused launch degrades to
# the next rung instead of killing the computation.
# ---------------------------------------------------------------------------

_FALLBACK_CACHE: Dict[tuple, ConvBackend] = {}


def fallback_backend(chain: Sequence[BackendLike], *,
                     on_fallback: Optional[Callable] = None) -> ConvBackend:
    """A `ConvBackend` that tries each backend in `chain` in order.

    Every op (plain, fused, and epilogue-fused) attempts the rungs left
    to right; an exception from rung i invokes
    ``on_fallback(backend_name, op_name, exc)`` (when given) and falls
    through to rung i+1.  When every rung fails the LAST exception
    propagates -- the ladder never silently swallows a total failure.

    Exceptions are caught EAGERLY, per call: under `jax.jit` a rung that
    raises at trace time degrades, but a rung whose failure only
    manifests at run time on device does not (trace-time dispatch cannot
    see it).  The serving engine therefore keeps per-attempt jitted
    functions and walks the ladder itself; this seam covers eager and
    trace-time failures for everyone else.

    Ladders without an `on_fallback` observer are memoized per chain, so
    repeated `resolve_backend(("pallas", "reference"))` calls return the
    SAME object -- `dispatch_backend`'s `_SHARDED_CACHE` (keyed on
    `id(base)`) and jit static-argument caching both stay effective."""
    entries: Tuple[BackendLike, ...] = tuple(chain)
    if not entries:
        raise ValueError("fallback chain must name at least one backend")

    cache_key = None
    if on_fallback is None:
        try:
            cache_key = tuple(
                e if isinstance(e, (str, bool, type(None))) else id(e)
                for e in entries)
        except TypeError:  # pragma: no cover - entries above always hashable
            cache_key = None
        hit = _FALLBACK_CACHE.get(cache_key) if cache_key else None
        if hit is not None:
            return hit

    backends = tuple(resolve_backend(b) for b in entries)

    def _run(op_name, call):
        last_exc = None
        for be in backends:
            try:
                return call(be)
            except Exception as exc:  # noqa: BLE001 - ladder catches all
                last_exc = exc
                if on_fallback is not None:
                    on_fallback(be.name, op_name, exc)
        raise last_exc

    ladder = ConvBackend(
        name=">".join(be.name for be in backends),
        forward=lambda x, w, spec: _run(
            "forward", lambda be: be.forward(x, w, spec)),
        input_grad=lambda dy, w, spec, n_out: _run(
            "input_grad", lambda be: be.input_grad(dy, w, spec, n_out)),
        filter_grad=lambda x, dy, spec: _run(
            "filter_grad", lambda be: be.filter_grad(x, dy, spec)),
        # Fused slots route through each rung's own METHOD (not the raw
        # fused callable): a rung without a fused kernel contributes its
        # two-launch composition instead of being skipped.
        fused_backward=lambda x, dy, w, spec, n_out: _run(
            "backward", lambda be: be.backward(x, dy, w, spec, n_out)),
        fused_ct_backward=lambda g, dy, w, spec: _run(
            "ct_backward", lambda be: be.ct_backward(g, dy, w, spec)),
        fused_forward_ep=lambda x, w, bias, spec, ep: _run(
            "forward_ep", lambda be: be.forward_ep(x, w, bias, spec, ep)),
        fused_input_grad_ep=lambda dy, w, bias, spec, n_out, ep: _run(
            "input_grad_ep",
            lambda be: be.input_grad_ep(dy, w, bias, spec, n_out, ep)),
        fused_backward_ep=lambda x, y, dy, w, spec, n_out, ep: _run(
            "backward_ep",
            lambda be: be.backward_ep(x, y, dy, w, spec, n_out, ep)),
        fused_ct_backward_ep=lambda g, z, dy, w, spec, ep: _run(
            "ct_backward_ep",
            lambda be: be.ct_backward_ep(g, z, dy, w, spec, ep)))
    if cache_key is not None:
        _FALLBACK_CACHE[cache_key] = ladder
    return ladder


# ---------------------------------------------------------------------------
# Default backends.  Registered lazily to avoid import cycles
# (core.ecoflow / kernels.ops import this module for ConvSpec).
# ---------------------------------------------------------------------------

_DEFAULTS_REGISTERED = False


def _ensure_default_backends() -> None:
    global _DEFAULTS_REGISTERED
    if _DEFAULTS_REGISTERED:
        return

    import jax

    from repro.core import ecoflow

    # -- reference: jax's own conv gradients (materializes zeros) ----------
    def _ref_forward(x, w, spec: ConvSpec):
        return ecoflow.direct_conv(x, w, spec.stride, spec.padding,
                                   dilation=spec.dilation)

    def _ref_input_grad(dy, w, spec: ConvSpec, n_out):
        nh, nw = _pair(n_out)
        x_shape = (dy.shape[0], nh, nw, w.shape[2])
        f = lambda x_: ecoflow.direct_conv(x_, w, spec.stride, spec.padding,
                                           dilation=spec.dilation)
        import jax.numpy as jnp
        _, vjp = jax.vjp(f, jnp.zeros(x_shape, dy.dtype))
        return vjp(dy)[0]

    def _ref_filter_grad(x, dy, spec: ConvSpec):
        kh, kw = spec.filter_shape
        w_shape = (kh, kw, x.shape[3], dy.shape[3])
        f = lambda w_: ecoflow.direct_conv(x, w_, spec.stride, spec.padding,
                                           dilation=spec.dilation)
        import jax.numpy as jnp
        _, vjp = jax.vjp(f, jnp.zeros(w_shape, x.dtype))
        return vjp(dy)[0]

    register_backend(ConvBackend("reference", _ref_forward,
                                 _ref_input_grad, _ref_filter_grad))

    # -- xla_zero_free: EcoFlow phase/tap decomposition in dense XLA -------
    def _xla_forward(x, w, spec: ConvSpec):
        if spec.dilation == (1, 1):
            return _ref_forward(x, w, spec)
        return ecoflow.dilated_forward_zero_free(
            x, w, stride=spec.stride, padding=spec.padding,
            dilation=spec.dilation)

    def _xla_input_grad(dy, w, spec: ConvSpec, n_out):
        return ecoflow.transposed_conv_zero_free(
            dy, w, stride=spec.stride, padding=spec.padding,
            n_out=_pair(n_out), dilation=spec.dilation)

    def _xla_filter_grad(x, dy, spec: ConvSpec):
        return ecoflow.dilated_conv_filter_grad_zero_free(
            x, dy, stride=spec.stride, padding=spec.padding,
            k=spec.filter_shape, dilation=spec.dilation)

    register_backend(ConvBackend("xla_zero_free", _xla_forward,
                                 _xla_input_grad, _xla_filter_grad))

    # -- pallas: fused single-launch kernels -------------------------------
    def _pl_forward(x, w, spec: ConvSpec):
        if spec.dilation == (1, 1):
            return _ref_forward(x, w, spec)
        from repro.kernels import ops as kops
        return kops.dconv_forward(x, w, stride=spec.stride,
                                  padding=spec.padding,
                                  dilation=spec.dilation)

    def _pl_input_grad(dy, w, spec: ConvSpec, n_out):
        # ONE launch for ANY (stride, dilation) pair, through the
        # per-geometry STRATEGY planner: `tiling.plan_strategy` races the
        # unified (phase, tap) decomposition against the predicated
        # implicit-GEMM kernel and the wrapper launches the winner --
        # both single-launch, so the jaxpr pins hold either way (see
        # DESIGN.md Sec. 2.5 / 2.10).  Ops implicit-GEMM does not cover
        # (forward, filter grad, the fused dual-gradient backwards below)
        # fall back to phase decomposition inside the planner.
        from repro.kernels import ops as kops
        return kops.tconv_phase(dy, w, stride=spec.stride,
                                padding=spec.padding, n_out=_pair(n_out),
                                dilation=spec.dilation)

    def _pl_filter_grad(x, dy, spec: ConvSpec):
        from repro.kernels import ops as kops
        return kops.dconv_filter_grad(x, dy, stride=spec.stride,
                                      padding=spec.padding,
                                      k=spec.filter_shape,
                                      dilation=spec.dilation)

    def _pl_backward(x, dy, w, spec: ConvSpec, n_out):
        from repro.kernels import ops as kops
        return kops.conv_backward(x, dy, w, stride=spec.stride,
                                  padding=spec.padding,
                                  n_out=_pair(n_out),
                                  dilation=spec.dilation)

    def _pl_ct_backward(g, dy, w, spec: ConvSpec):
        from repro.kernels import ops as kops
        return kops.tconv_backward(g, dy, w, stride=spec.stride,
                                   padding=spec.padding,
                                   dilation=spec.dilation)

    # Epilogue-fused launches.  Note the forward: the plain pallas forward
    # defers dilation (1, 1) to XLA, but with an epilogue requested the
    # (dilation-general) Pallas kernel is always used so the tail is fused
    # into the single conv launch.
    def _pl_forward_ep(x, w, bias, spec: ConvSpec, ep: Epilogue):
        from repro.kernels import ops as kops
        return kops.dconv_forward(x, w, stride=spec.stride,
                                  padding=spec.padding,
                                  dilation=spec.dilation,
                                  bias=bias, epilogue=ep)

    def _pl_input_grad_ep(dy, w, bias, spec: ConvSpec, n_out,
                          ep: Epilogue):
        from repro.kernels import ops as kops
        return kops.tconv_phase(dy, w, stride=spec.stride,
                                padding=spec.padding, n_out=_pair(n_out),
                                dilation=spec.dilation,
                                bias=bias, epilogue=ep)

    def _pl_backward_ep(x, y, dy, w, spec: ConvSpec, n_out, ep: Epilogue):
        from repro.kernels import ops as kops
        return kops.conv_backward(x, dy, w, stride=spec.stride,
                                  padding=spec.padding, n_out=_pair(n_out),
                                  dilation=spec.dilation,
                                  y=y, epilogue=ep)

    def _pl_ct_backward_ep(g, z, dy, w, spec: ConvSpec, ep: Epilogue):
        from repro.kernels import ops as kops
        return kops.tconv_backward(g, dy, w, stride=spec.stride,
                                   padding=spec.padding,
                                   dilation=spec.dilation,
                                   z=z, epilogue=ep)

    register_backend(ConvBackend("pallas", _pl_forward,
                                 _pl_input_grad, _pl_filter_grad,
                                 fused_backward=_pl_backward,
                                 fused_ct_backward=_pl_ct_backward,
                                 fused_forward_ep=_pl_forward_ep,
                                 fused_input_grad_ep=_pl_input_grad_ep,
                                 fused_backward_ep=_pl_backward_ep,
                                 fused_ct_backward_ep=_pl_ct_backward_ep))

    # Only mark done once every default registered -- a failure above
    # surfaces on the next call instead of poisoning the registry.
    _DEFAULTS_REGISTERED = True


# ---------------------------------------------------------------------------
# Sharding-aware dispatch: shard_map'd launches on a multi-device mesh
# (DESIGN.md Sec. 2.9).
# ---------------------------------------------------------------------------

def dispatch_backend(backend: BackendLike) -> ConvBackend:
    """Mesh-aware `resolve_backend`.

    Outside a `repro.parallel.sharding.use_mesh` context (or on a 1-chip
    mesh) this IS `resolve_backend` -- the single-device jaxpr is
    byte-identical to before.  Under an active multi-device mesh it wraps
    the resolved backend so every conv op launches through `shard_map`
    with locally-shaped blocks: batch sharded over the logical "dp" axes,
    channels over "tp", explicit psums for the reduced gradients.  The
    mesh is read at TRACE time, so jitted steps must trace under
    `use_mesh` (the model step helpers do)."""
    be = resolve_backend(backend)
    try:
        from repro.parallel import sharding as _sh
    except Exception:  # pragma: no cover - parallel pkg always present
        return be
    mesh = _sh.current_mesh()
    if mesh is None or mesh.size <= 1:
        return be
    return sharded_backend(be, mesh)


_SHARDED_CACHE: Dict[tuple, ConvBackend] = {}


def sharded_backend(base: ConvBackend, mesh) -> ConvBackend:
    """shard_map wrapper around `base` for `mesh` (memoized per pair).

    Per-op sharding scheme -- chosen so NO forward-path psum is ever
    needed, which keeps nonlinear epilogues correct (they must see exact
    sums, so only NON-contracted dims may shard):

      forward / forward_ep       x:(B@dp,..)  w:(..,Cin,Cout@tp) -> y@(dp,tp)
      input_grad / _ep (tconv)   dy:(B@dp,..) w:(..,Cin@tp,Cout) -> dx@(dp,tp)
      backward / backward_ep     per-shard fused launch, then
                                 psum(dx, tp) + psum(dW/db, dp)
      ct_backward / _ep          per-shard fused launch, then
                                 psum(ddy, tp) + psum(dW/db, dp)
      filter_grad                psum(dW, dp)

    Each axis is applied only when it divides the corresponding global
    dim (same guard policy as `parallel.sharding._guard`); when neither
    axis applies the base backend runs replicated with no shard_map.
    `check_vma=False` because pallas_call has no replication rule.  The
    base backend's methods run INSIDE the shard_map body, so its
    fused-vs-two-launch fallback and `tiling.plan_tiles` both see LOCAL
    shapes -- one forward and one backward pallas_call per shard."""
    key = (id(base), mesh)
    hit = _SHARDED_CACHE.get(key)
    if hit is not None:
        return hit

    import jax
    from jax.sharding import PartitionSpec as P

    from repro.parallel import sharding as _sh

    la = _sh.logical_axes(mesh)
    dp_axes, tp_axes = la["dp"], la["tp"]

    def _ax(axes, dim):
        """`axes` if it is real (>1 devices) and divides `dim`."""
        if axes is None:
            return None
        n = _sh._axis_size(mesh, axes)
        return axes if n > 1 and dim % n == 0 else None

    def _launch(body, in_specs, out_specs, *args):
        return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)(*args)

    def _psum(v, axes):
        return jax.lax.psum(v, axes) if axes is not None else v

    # -- forward family: shard the produced dims, contract full ones ------

    def forward(x, w, spec):
        bd, cd = _ax(dp_axes, x.shape[0]), _ax(tp_axes, w.shape[3])
        if bd is None and cd is None:
            return base.forward(x, w, spec)
        return _launch(lambda x_, w_: base.forward(x_, w_, spec),
                       (P(bd, None, None, None), P(None, None, None, cd)),
                       P(bd, None, None, cd), x, w)

    def forward_ep(x, w, bias, spec, ep):
        bd, cd = _ax(dp_axes, x.shape[0]), _ax(tp_axes, w.shape[3])
        if bd is None and cd is None:
            return base.forward_ep(x, w, bias, spec, ep)
        if bias is None:
            return _launch(
                lambda x_, w_: base.forward_ep(x_, w_, None, spec, ep),
                (P(bd, None, None, None), P(None, None, None, cd)),
                P(bd, None, None, cd), x, w)
        return _launch(
            lambda x_, w_, b_: base.forward_ep(x_, w_, b_, spec, ep),
            (P(bd, None, None, None), P(None, None, None, cd), P(cd)),
            P(bd, None, None, cd), x, w, bias)

    # tconv-as-a-layer: the produced channel dim is Cin (w.shape[2]); the
    # contracted Cout stays full per shard, so the epilogue bias (a
    # per-Cin vector here) applies to exact sums.

    def input_grad(dy, w, spec, n_out):
        bd, cd = _ax(dp_axes, dy.shape[0]), _ax(tp_axes, w.shape[2])
        if bd is None and cd is None:
            return base.input_grad(dy, w, spec, n_out)
        return _launch(
            lambda dy_, w_: base.input_grad(dy_, w_, spec, n_out),
            (P(bd, None, None, None), P(None, None, cd, None)),
            P(bd, None, None, cd), dy, w)

    def input_grad_ep(dy, w, bias, spec, n_out, ep):
        bd, cd = _ax(dp_axes, dy.shape[0]), _ax(tp_axes, w.shape[2])
        if bd is None and cd is None:
            return base.input_grad_ep(dy, w, bias, spec, n_out, ep)
        if bias is None:
            return _launch(
                lambda dy_, w_: base.input_grad_ep(dy_, w_, None, spec,
                                                   n_out, ep),
                (P(bd, None, None, None), P(None, None, cd, None)),
                P(bd, None, None, cd), dy, w)
        return _launch(
            lambda dy_, w_, b_: base.input_grad_ep(dy_, w_, b_, spec,
                                                   n_out, ep),
            (P(bd, None, None, None), P(None, None, cd, None), P(cd)),
            P(bd, None, None, cd), dy, w, bias)

    # -- backward family: per-shard fused launch + explicit psums ---------
    # dx/ddy are partial over the sharded channel dim (tp); dW/db are
    # partial over the batch shards (dp).  The psums sit OUTSIDE the
    # pallas_call but inside the shard_map body, so each conv layer still
    # lowers to exactly one backward launch per shard.

    def filter_grad(x, dy, spec):
        bd, cd = _ax(dp_axes, x.shape[0]), _ax(tp_axes, dy.shape[3])
        if bd is None and cd is None:
            return base.filter_grad(x, dy, spec)
        return _launch(
            lambda x_, dy_: _psum(base.filter_grad(x_, dy_, spec), bd),
            (P(bd, None, None, None), P(bd, None, None, cd)),
            P(None, None, None, cd), x, dy)

    def backward(x, dy, w, spec, n_out):
        bd, cd = _ax(dp_axes, x.shape[0]), _ax(tp_axes, w.shape[3])
        if bd is None and cd is None:
            return base.backward(x, dy, w, spec, n_out)

        def body(x_, dy_, w_):
            dx, dw = base.backward(x_, dy_, w_, spec, n_out)
            return _psum(dx, cd), _psum(dw, bd)

        return _launch(body,
                       (P(bd, None, None, None), P(bd, None, None, cd),
                        P(None, None, None, cd)),
                       (P(bd, None, None, None), P(None, None, None, cd)),
                       x, dy, w)

    def backward_ep(x, y, dy, w, spec, n_out, ep):
        bd, cd = _ax(dp_axes, x.shape[0]), _ax(tp_axes, w.shape[3])
        if bd is None and cd is None:
            return base.backward_ep(x, y, dy, w, spec, n_out, ep)

        def body(x_, dy_, w_, *rest):
            y_ = rest[0] if ep.needs_y else None
            dx, dw, db = base.backward_ep(x_, y_, dy_, w_, spec, n_out, ep)
            dx, dw = _psum(dx, cd), _psum(dw, bd)
            if db is None:
                return dx, dw
            return dx, dw, _psum(db, bd)

        in_specs = [P(bd, None, None, None), P(bd, None, None, cd),
                    P(None, None, None, cd)]
        args = [x, dy, w]
        if ep.needs_y:
            in_specs.append(P(bd, None, None, cd))
            args.append(y)
        out_specs = (P(bd, None, None, None), P(None, None, None, cd))
        if ep.bias:
            out_specs = out_specs + (P(cd),)
        out = _launch(body, tuple(in_specs), out_specs, *args)
        return out if ep.bias else (out[0], out[1], None)

    def ct_backward(g, dy, w, spec):
        bd, cd = _ax(dp_axes, g.shape[0]), _ax(tp_axes, w.shape[2])
        if bd is None and cd is None:
            return base.ct_backward(g, dy, w, spec)

        def body(g_, dy_, w_):
            ddy, dw = base.ct_backward(g_, dy_, w_, spec)
            return _psum(ddy, cd), _psum(dw, bd)

        return _launch(body,
                       (P(bd, None, None, cd), P(bd, None, None, None),
                        P(None, None, cd, None)),
                       (P(bd, None, None, None), P(None, None, cd, None)),
                       g, dy, w)

    def ct_backward_ep(g, z, dy, w, spec, ep):
        bd, cd = _ax(dp_axes, g.shape[0]), _ax(tp_axes, w.shape[2])
        if bd is None and cd is None:
            return base.ct_backward_ep(g, z, dy, w, spec, ep)

        def body(g_, dy_, w_, *rest):
            z_ = rest[0] if ep.needs_y else None
            ddy, dw, db = base.ct_backward_ep(g_, z_, dy_, w_, spec, ep)
            ddy, dw = _psum(ddy, cd), _psum(dw, bd)
            if db is None:
                return ddy, dw
            return ddy, dw, _psum(db, bd)

        in_specs = [P(bd, None, None, cd), P(bd, None, None, None),
                    P(None, None, cd, None)]
        args = [g, dy, w]
        if ep.needs_y:
            in_specs.append(P(bd, None, None, cd))
            args.append(z)
        out_specs = (P(bd, None, None, None), P(None, None, cd, None))
        if ep.bias:
            out_specs = out_specs + (P(cd),)
        out = _launch(body, tuple(in_specs), out_specs, *args)
        return out if ep.bias else (out[0], out[1], None)

    wrapped = ConvBackend(
        name=f"{base.name}@shard",
        forward=forward,
        input_grad=input_grad,
        filter_grad=filter_grad,
        # All fused slots filled so the ConvBackend methods always route
        # to the shard_map wrappers; the base backend's own
        # fused-vs-two-launch choice happens inside the body.
        fused_backward=backward,
        fused_ct_backward=ct_backward,
        fused_forward_ep=forward_ep,
        fused_input_grad_ep=input_grad_ep,
        fused_backward_ep=backward_ep,
        fused_ct_backward_ep=ct_backward_ep)
    _SHARDED_CACHE[key] = wrapped
    return wrapped
