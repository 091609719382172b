"""pix2pix's U-Net generator on the EcoFlow conv dispatch.

The `unet_256` generator of Isola et al., "Image-to-Image Translation
with Conditional Adversarial Networks" (arXiv:1611.07004), as the
authors' `UnetGenerator(input_nc, output_nc, num_downs, ngf)` builds it
(`models/networks.py` of junyanz/pytorch-CycleGAN-and-pix2pix).  Every
filter is 4x4, stride 2, padding 1.  With n = num_downs levels:

  encoder   h1 = conv(x)
            h_k = BN(conv(LReLU_0.2(h_{k-1})))       k = 2 .. n-1
            h_n = conv(LReLU_0.2(h_{n-1}))           (no norm)
  decoder   u_n = BN(tconv(ReLU(h_n)))
            u_k = BN(tconv(ReLU(cat(h_k, u_{k+1}))))  k = n-1 .. 2
            out = tanh(tconv(ReLU(cat(h_1, u_2))))

Encoder widths are ngf * (1, 2, 4, 8, 8, ...) and each decoder level
maps back to the width of the encoder level above it; d1 maps to
`out_ch`.  Only the outermost transposed conv carries a bias of its
own: the authors build every other conv with `bias=False` under batch
norm, and the two encoder convs without a norm have none either.

How the launches realise it:

  * Batch norm runs in inference mode, folded into each conv's weights
    and a per-channel bias (`fold_batch_norm`), which rides the conv's
    fused epilogue.  Dropout (the authors' three inner 512-wide decoder
    levels) is off: an answer is a function of its image.
  * Each encoder conv fuses the activation its consumers need into its
    epilogue: LeakyReLU(0.2) for levels 1 .. n-1, whose output
    LReLU(h_k) feeds the next encoder level, and ReLU for level n,
    whose only consumer is the innermost decoder's ReLU.
  * The skip half of each concatenation then holds LReLU(h_k), and
    ReLU(LReLU(h_k)) = ReLU(h_k), so ReLU(cat(h_k, u)) is
    cat(ReLU(LReLU(h_k)), ReLU(u)): the skip's ReLU is applied in XLA
    inside the concatenation, and ReLU(u) is the previous decoder
    level's fused epilogue.  The last decoder level fuses tanh.

So each of the 2n convs is one fused Pallas launch on the `pallas`
backend: the encoder's strided convs through `kernels/dconv_forward.py`
(space-to-depth packed, stride 1), the decoder's through
`kernels/tconv_phase.py`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.conv import ecoflow_conv, ecoflow_conv_transpose
from repro.core.spec import ConvSpec, Epilogue

_LEAKY = Epilogue(activation="leaky_relu", slope=0.2)
_LEAKY_B = Epilogue(activation="leaky_relu", slope=0.2, bias=True)
_RELU = Epilogue(activation="relu")
_RELU_B = Epilogue(activation="relu", bias=True)
_TANH_B = Epilogue(activation="tanh", bias=True)
_SPEC = ConvSpec.make(stride=2, padding=1, filter_shape=4)
BN_EPS = 1e-5


def unet_widths(*, in_ch: int, num_downs: int, ngf: int) -> list:
    """Channel widths of the encoder's inputs and outputs: entry k is
    the width of h_k (entry 0 the image's)."""
    return [in_ch] + [ngf * min(2 ** k, 8) for k in range(num_downs)]


def fold_batch_norm(w, gamma, beta, mean, var, *, axis: int):
    """Inference-mode batch norm after a conv, folded into it: (w * s,
    beta - mean * s) with s = gamma / sqrt(var + eps) scaling the filter
    along its output-channel `axis`."""
    s = gamma / jnp.sqrt(var + BN_EPS)
    shape = [1] * w.ndim
    shape[axis] = -1
    return w * s.reshape(shape), beta - mean * s


def _depth(params) -> int:
    return sum(1 for k in params if k.startswith("down"))


def _down_epilogue(k: int, n: int) -> Epilogue:
    """Encoder level k's fused tail; levels 1 and n have no norm, so no
    bias."""
    return _RELU if k == n else (_LEAKY if k == 1 else _LEAKY_B)


def _up_epilogue(k: int) -> Epilogue:
    return _TANH_B if k == 1 else _RELU_B


def unet_init(rng, *, in_ch: int = 3, out_ch: int = 3, num_downs: int = 8,
              ngf: int = 64):
    """Seeded weights, batch norm folded in: `down<k>` holds the encoder
    conv filter `w` (4, 4, Cin, Cout) and, where a norm follows, its
    folded bias `b`; `up<k>` the transposed conv's filter, stored as the
    filter of the direct conv whose input gradient it is ((4, 4, Cout,
    Cin), the upsampled side first), and its bias."""
    ch = unet_widths(in_ch=in_ch, num_downs=num_downs, ngf=ngf)
    n = num_downs
    params = {}
    keys = iter(jax.random.split(rng, 4 * n))

    def weight(shape, fan_in):   # He scale: activations keep their size
        return math.sqrt(2.0 / fan_in) * jax.random.truncated_normal(
            next(keys), -2.0, 2.0, shape, jnp.float32)

    def norm(w, c, axis):
        g, b, m, v = jax.random.normal(next(keys), (4, c), jnp.float32)
        return fold_batch_norm(w, 1.0 + 0.1 * g, 0.1 * b, 0.1 * m,
                               1.0 + 0.1 * jnp.abs(v), axis=axis)

    for k in range(1, n + 1):
        w = weight((4, 4, ch[k - 1], ch[k]), 16 * ch[k - 1])
        if k in (1, n):
            params[f"down{k}"] = {"w": w}
        else:
            w, b = norm(w, ch[k], axis=3)
            params[f"down{k}"] = {"w": w, "b": b}
    for k in range(n, 0, -1):
        cin = ch[k] if k == n else 2 * ch[k]
        cout = out_ch if k == 1 else ch[k - 1]
        w = weight((4, 4, cout, cin), 16 * cin)
        if k == 1:
            b = 0.1 * jax.random.normal(next(keys), (cout,), jnp.float32)
        else:
            w, b = norm(w, cout, axis=2)
        params[f"up{k}"] = {"w": w, "b": b}
    return params


def unet_apply(params, x, *, backend=None):
    """images (B, H, W, in_ch), H and W multiples of 2**num_downs ->
    (B, H, W, out_ch) in (-1, 1).  `backend` selects the conv dispatch
    backend (see repro.core.spec)."""
    n = _depth(params)
    acts = []
    h = x
    for k in range(1, n + 1):
        p = params[f"down{k}"]
        with jax.named_scope(f"unet.down{k}"):
            h = ecoflow_conv(h, p["w"], 2, 1, backend, bias=p.get("b"),
                             epilogue=_down_epilogue(k, n))
        acts.append(h)
    u = acts.pop()
    for k in range(n, 0, -1):
        p = params[f"up{k}"]
        with jax.named_scope(f"unet.up{k}"):
            if k < n:
                u = jnp.concatenate([jax.nn.relu(acts[k - 1]), u], axis=-1)
            u = ecoflow_conv_transpose(
                u, p["w"], 2, 1, n_out=(2 * u.shape[1], 2 * u.shape[2]),
                backend=backend, bias=p["b"], epilogue=_up_epilogue(k))
    return u


def unet_plan_requests(params, batch_shape):
    """Tile-planning warmup entries for one serving bucket of the U-Net:
    one `"forward"` entry per encoder conv and one `"input_grad"` entry
    per decoder transposed conv, in the `(op, spec, x_shape, dy_shape,
    epilogue)` form `kernels.tiling.warmup_plans` consumes, in the order
    `unet_apply` launches them.  `batch_shape` is the launch's input
    shape (B, H, W, C)."""
    b, hh, ww, _ = (int(s) for s in batch_shape)
    n = _depth(params)
    entries = []
    for k in range(1, n + 1):
        w = params[f"down{k}"]["w"]
        entries.append(("forward", _SPEC,
                        (b, hh >> (k - 1), ww >> (k - 1), int(w.shape[2])),
                        (b, hh >> k, ww >> k, int(w.shape[3])),
                        _down_epilogue(k, n)))
    for k in range(n, 0, -1):
        w = params[f"up{k}"]["w"]
        entries.append(("input_grad", _SPEC,
                        (b, hh >> (k - 1), ww >> (k - 1), int(w.shape[2])),
                        (b, hh >> k, ww >> k, int(w.shape[3])),
                        _up_epilogue(k)))
    return entries
