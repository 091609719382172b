"""The pix2pix U-Net generator of `pix2pix-unet256`: what the benchmark
knows of this model kind.

Served, the generator maps an image in [-1, 1] to an image (the engine's
`unet` request).  With n = num_downs levels, every filter 4x4, stride 2,
padding 1 (Isola et al., arXiv:1611.07004; `UnetGenerator` in
`models/networks.py` of junyanz/pytorch-CycleGAN-and-pix2pix):

  h1 = conv(x);  h_k = BN(conv(LReLU_0.2(h_{k-1}))), k = 2 .. n-1;
  h_n = conv(LReLU_0.2(h_{n-1}));  u_n = BN(tconv(ReLU(h_n)));
  u_k = BN(tconv(ReLU(cat(h_k, u_{k+1})))), k = n-1 .. 2;
  out = tanh(tconv(ReLU(cat(h_1, u_2))) + b).

Batch norm is in inference mode and folded into the weights when they
are made (scale into the filter, shift into a bias), as the program is
handed them; dropout is off.

Weights, payloads and the plain reference are made here from the seed;
`ops` counts one launch's useful work for the readers.
"""
from __future__ import annotations

import math
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import Op, _conv
from bench.reference import EXACT, _from_seed, _w, conv, tconv

REQUEST_KIND = "unet"
# The kind's tiny configuration, for CPU rehearsals of the drivers.
TINY = {"kind": "unet", "image": 32, "in_ch": 3, "out_ch": 3,
        "num_downs": 5, "ngf": 4}
BN_EPS = 1e-5


def widths(model: Dict) -> List[int]:
    """Entry k: the channels of h_k (entry 0: the image's)."""
    ngf = model["ngf"]
    return [model["in_ch"]] + [ngf * min(2 ** k, 8)
                               for k in range(model["num_downs"])]


def payload_shape(model: Dict) -> tuple:
    return (model["image"], model["image"], model["in_ch"])


def payload_pool(model: Dict, seed: int, n: int) -> np.ndarray:
    """`n` distinct images with values in [-1, 1], the range pix2pix
    feeds its generator, made on the device from the seed.  They are
    made as rows of pixels and shaped on the host: a device array whose
    last axis is 3 channels would be laid out 128 lanes wide, 43 times
    its size (8.6 GB for 256 images)."""
    shape = (n, *payload_shape(model))
    flat = _from_seed(lambda key: jax.random.uniform(
        jax.random.fold_in(key, 1000), (n, math.prod(shape[1:])),
        jnp.float32, -1.0, 1.0), seed)
    return np.asarray(flat).reshape(shape)


def engine_kwargs(model: Dict, params: Dict) -> Dict:
    return {"unet_params": params}


def forward(model: Dict, params, batch, num):
    return unet_apply(params, batch, n=model["num_downs"], num=num)


def params(model: Dict, seed: int) -> Dict:
    """Generator weights in the program's layout: `down<k>` the encoder
    filter `w` (4, 4, Cin, Cout) and, under a norm, its bias `b`;
    `up<k>` each transposed conv stored as the filter of the direct conv
    whose input gradient it is ((4, 4, Cout, Cin)) and its bias.  Filters
    are truncated normals at He scale, so that activations keep their
    size through the depth; the norms' statistics are drawn near the
    identity and folded in."""
    n, ch, out_ch = model["num_downs"], widths(model), model["out_ch"]

    def fold(key, tag, w, axis):
        g, b, m, v = jax.random.normal(jax.random.fold_in(key, tag),
                                       (4, w.shape[axis]), jnp.float32)
        s = (1.0 + 0.1 * g) / jnp.sqrt(1.0 + 0.1 * jnp.abs(v) + BN_EPS)
        shape = [1, 1, 1, 1]
        shape[axis] = -1
        return {"w": w * s.reshape(shape), "b": 0.1 * b - 0.1 * m * s}

    def make(key):
        p = {}
        for k in range(1, n + 1):
            w = _w(key, k, (4, 4, ch[k - 1], ch[k]), 8 * ch[k - 1])
            p[f"down{k}"] = {"w": w} if k in (1, n) else \
                fold(key, 100 + k, w, 3)
        for k in range(n, 0, -1):
            cin = ch[k] if k == n else 2 * ch[k]
            cout = out_ch if k == 1 else ch[k - 1]
            w = _w(key, 200 + k, (4, 4, cout, cin), 8 * cin)
            p[f"up{k}"] = fold(key, 300 + k, w, 2) if k > 1 else {
                "w": w, "b": 0.1 * jax.random.normal(
                    jax.random.fold_in(key, 301), (cout,), jnp.float32)}
        return p
    return _from_seed(make, seed)


def unet_apply(params, x, *, n: int, num=EXACT):
    """The equations above, each conv's bias (its folded norm) added
    after the product, and each level's output stored."""
    def level(op, h, p):
        y = op(h, p["w"], 2, 1, num=num)
        return y + p["b"] if "b" in p else y

    hs = [num.st(level(conv, x, params["down1"]))]
    for k in range(2, n + 1):
        hs.append(num.st(level(conv, jax.nn.leaky_relu(hs[-1], 0.2),
                               params[f"down{k}"])))
    u = jax.nn.relu(hs[n - 1])
    for k in range(n, 1, -1):
        if k < n:
            u = jax.nn.relu(jnp.concatenate([hs[k - 1], u], axis=-1))
        u = num.st(level(tconv, u, params[f"up{k}"]))
    u = jax.nn.relu(jnp.concatenate([hs[0], u], axis=-1))
    return num.st(jnp.tanh(level(tconv, u, params["up1"])))


def ops(model: Dict, batch: int) -> List[Op]:
    """One generator forward at `batch` images: the n strided encoder
    convs `down1..down<n>` and the n transposed decoder convs
    `up<n>..up1`, each counted as the direct conv whose input gradient
    it is.  All are Pallas launches."""
    n, ch, out_ch = model["num_downs"], widths(model), model["out_ch"]
    s, b = model["image"], batch
    out = []
    for k in range(1, n + 1):
        i, o = s >> (k - 1), s >> k
        out.append(_conv(f"down{k}", b, (o, o), 4, ch[k - 1], ch[k],
                         (b, i, i, ch[k - 1]), (b, o, o, ch[k]), "pallas"))
    for k in range(n, 0, -1):
        big, o = s >> (k - 1), s >> k
        cin = ch[k] if k == n else 2 * ch[k]
        cout = out_ch if k == 1 else ch[k - 1]
        out.append(_conv(f"up{k}", b, (o, o), 4, cout, cin,
                         (b, big, big, cout), (b, o, o, cin), "pallas"))
    return out
