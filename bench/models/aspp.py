"""The DeepLabv3 ASPP head of `deeplabv3-aspp`: what the benchmark knows
of this model kind.

Served, the head maps a block-4 feature map to class scores (the
engine's `aspp` request): one 3x3 atrous branch per rate, their ReLUs
concatenated, and a 1x1 classifier.

Weights, payloads and the plain reference are made here from the seed;
`ops` counts one launch's useful work for the readers.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import jax.numpy as jnp

from bench.flops import Op, _conv
from bench.reference import EXACT, _from_seed, _w, conv

REQUEST_KIND = "aspp"
# The kind's tiny configuration, for CPU rehearsals of the drivers.
TINY = {"kind": "aspp", "in_ch": 8, "feature_hw": [9, 9], "width": 8,
        "rates": [1, 2, 3], "n_classes": 5}


def payload_shape(model: Dict) -> tuple:
    h, w = model["feature_hw"]
    return (h, w, model["in_ch"])


def payload_pool(model: Dict, seed: int, n: int) -> jax.Array:
    """`n` distinct post-ReLU feature maps (what a ResNet block 4 emits)
    made on the device from the seed."""
    shape = (n, *model["feature_hw"], model["in_ch"])
    return _from_seed(lambda key: jax.nn.relu(jax.random.normal(
        jax.random.fold_in(key, 1000), shape, jnp.float32)), seed)


def engine_kwargs(model: Dict, params: Dict) -> Dict:
    return {"aspp_params": params, "rates": tuple(model["rates"])}


def forward(model: Dict, params, batch, num):
    return aspp_apply(params, batch, rates=tuple(model["rates"]), num=num)


def params(model: Dict, seed: int) -> Dict:
    """ASPP head weights in the program's layout: one 3x3 filter per
    rate (`rate<r>`) and the 1x1 classifier (`fuse`)."""
    c, width, ncls = model["in_ch"], model["width"], model["n_classes"]
    rates = model["rates"]

    def make(key):
        p = {f"rate{r}": _w(key, i, (3, 3, c, width), 9 * c)
             for i, r in enumerate(rates)}
        p["fuse"] = _w(key, 97, (1, 1, width * len(rates), ncls),
                       width * len(rates))
        return p
    return _from_seed(make, seed)


def aspp_apply(params, x, *, rates, num=EXACT):
    feats = [num.st(jax.nn.relu(conv(x, params[f"rate{r}"], 1, r, r,
                                     num=num))) for r in rates]
    return conv(jnp.concatenate(feats, axis=-1), params["fuse"], 1, 0,
                num=num)


def ops(model: Dict, batch: int) -> List[Op]:
    """One ASPP head forward at `batch` feature maps: a 3x3 atrous branch
    per rate (same padding, full resolution) and the 1x1 classifier over
    their concatenation.  The classifier has no fused epilogue, so the
    program hands it to XLA."""
    h, w = model["feature_hw"]
    c, width, ncls = model["in_ch"], model["width"], model["n_classes"]
    rates = model["rates"]
    b = batch
    ops = [_conv(f"rate{r}", b, (h, w), 3, c, width, (b, h, w, c),
                 (b, h, w, width), "pallas") for r in rates]
    ops.append(_conv("classifier", b, (h, w), 1, width * len(rates), ncls,
                     (b, h, w, width * len(rates)), (b, h, w, ncls), "xla"))
    return ops
