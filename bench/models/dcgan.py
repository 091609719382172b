"""The DCGAN of `dcgan32`: what the benchmark knows of this model kind.

Served, the generator maps a latent to a 32x32 image (the engine's
`gan_gen` request): a dense projection to a 4x4x(2*base) map and three
4x4 stride-2 transposed convs.  Trained, the GAN adds a discriminator of
three 4x4 stride-2 convs and a dense head, stepped by simultaneous SGD.

Weights, payloads and the plain reference are made here from the seed;
`ops` counts one launch's useful work for the readers.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench.flops import Op, _conv, _dense
from bench.reference import EXACT, _from_seed, _w, conv, dot, tconv

REQUEST_KIND = "gan_gen"
# The kind's tiny configuration, for CPU rehearsals of the drivers.
TINY = {"kind": "dcgan", "z_dim": 8, "base": 16, "image": 32,
        "channels": 3}


# -- serving -----------------------------------------------------------------

def payload_shape(model: Dict) -> tuple:
    return (model["z_dim"],)


def payload_pool(model: Dict, seed: int, n: int) -> jax.Array:
    """`n` distinct latents made on the device from the seed."""
    shape = (n, model["z_dim"])
    return _from_seed(lambda key: jax.random.normal(
        jax.random.fold_in(key, 1000), shape, jnp.float32), seed)


def engine_kwargs(model: Dict, params: Dict) -> Dict:
    return {"gan_params": params}


def forward(model: Dict, params, batch, num):
    return generator_apply(params, batch, num=num)


# -- weights -----------------------------------------------------------------

def _generator(model: Dict, key) -> Dict:
    z, base, ch = model["z_dim"], model["base"], model["channels"]
    return {
        "proj": _w(key, 0, (z, 16 * 2 * base), z),
        "t1": _w(key, 1, (4, 4, base, 2 * base), 16 * base),
        "t2": _w(key, 2, (4, 4, base // 2, base), 8 * base),
        "t3": _w(key, 3, (4, 4, ch, base // 2), 16 * ch),
    }


def params(model: Dict, seed: int) -> Dict:
    """DCGAN generator weights in the program's layout: `proj` maps the
    latent to a 4x4x(2*base) map, and each transposed-conv filter is
    stored as the (K, K, Cin, Cout) filter of the direct conv whose
    input gradient it is (Cin the upsampled side)."""
    return _from_seed(lambda key: _generator(model, key), seed)


def gan_params(model: Dict, seed: int) -> Dict:
    """The whole DCGAN training state, {"g": generator, "d":
    discriminator}: three 4x4 stride-2 convs (3 -> base/2 -> base ->
    2*base) and a dense head on the 4x4x(2*base) map."""
    base, ch = model["base"], model["channels"]

    def make(key):
        d = {"c1": _w(key, 11, (4, 4, ch, base // 2), 16 * ch),
             "c2": _w(key, 12, (4, 4, base // 2, base), 8 * base),
             "c3": _w(key, 13, (4, 4, base, 2 * base), 16 * base),
             "head": _w(key, 14, (16 * 2 * base, 1), 16 * 2 * base)}
        return {"g": _generator(model, key), "d": d}
    return _from_seed(make, seed)


def train_batch(model: Dict, seed: int, step: int) -> Dict[str, np.ndarray]:
    """The GAN's inputs at `step`, a function of (seed, step) alone:
    latents and "real" images in [-1, 1], made on the host as the
    trainer's feed is."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 5, step]))
    b, c = model["batch"], model["channels"]
    z = rng.standard_normal((b, model["z_dim"]), np.float32)
    real = np.tanh(rng.standard_normal((b, 32, 32, c), np.float32))
    return {"z": z, "real": real}


# -- the math ----------------------------------------------------------------

def generator_apply(params, z, *, num=EXACT):
    b = z.shape[0]
    act = lambda f, x: num.st(f(x))
    x = act(jax.nn.relu, dot(z, params["proj"], num=num).reshape(b, 4, 4, -1))
    x = act(jax.nn.relu, tconv(x, params["t1"], 2, 1, num=num))
    x = act(jax.nn.relu, tconv(x, params["t2"], 2, 1, num=num))
    return act(jnp.tanh, tconv(x, params["t3"], 2, 1, num=num))


def discriminator_apply(params, x, *, num=EXACT):
    for name in ("c1", "c2", "c3"):
        x = num.st(jax.nn.leaky_relu(conv(x, params[name], 2, 1, num=num),
                                     0.2))
    return dot(x.reshape(x.shape[0], -1), params["head"], num=num)


def gan_step(state, z, real, lr, *, num=EXACT):
    """One simultaneous SGD step of the non-saturating GAN, each side's
    gradient taken against the other side's pre-step weights:
    (new_state, g_loss, d_loss, grads)."""
    sp = lambda x: num.st(jax.nn.softplus(x))

    def g_loss(g):
        fake = generator_apply(g, z, num=num)
        return sp(-discriminator_apply(state["d"], fake, num=num)).mean()

    def d_loss(d):
        fake = generator_apply(state["g"], z, num=num)
        return (sp(-discriminator_apply(d, real, num=num)).mean()
                + sp(discriminator_apply(d, fake, num=num)).mean())

    gl, gg = jax.value_and_grad(g_loss)(state["g"])
    dl, dg = jax.value_and_grad(d_loss)(state["d"])
    grads = {"g": gg, "d": dg}
    new = jax.tree.map(lambda p, g: num.st(p - lr * g), state, grads)
    return new, gl, dl, grads


# -- useful work -------------------------------------------------------------

def ops(model: Dict, batch: int) -> List[Op]:
    """One generator forward at `batch` latents: the projection and the
    three 4x4 stride-2 transposed convs (4 -> 8 -> 16 -> 32)."""
    z, base, ch = model["z_dim"], model["base"], model["channels"]
    b = batch
    ops = [_dense("proj", b, z, 16 * 2 * base)]
    # (name, small side, Cin of the direct conv (upsampled side), Cout)
    for name, o, cin, cout in (("t1", 4, base, 2 * base),
                               ("t2", 8, base // 2, base),
                               ("t3", 16, ch, base // 2)):
        ops.append(_conv(name, b, (o, o), 4, cin, cout,
                         (b, o, o, cout), (b, 2 * o, 2 * o, cin), "pallas"))
    return ops


def discriminator_ops(model: Dict, batch: int) -> List[Op]:
    """One discriminator forward: three 4x4 stride-2 convs (32 -> 16 ->
    8 -> 4) and the dense head."""
    base, ch = model["base"], model["channels"]
    b = batch
    ops = []
    for name, o, cin, cout in (("c1", 16, ch, base // 2),
                               ("c2", 8, base // 2, base),
                               ("c3", 4, base, 2 * base)):
        ops.append(_conv(name, b, (o, o), 4, cin, cout,
                         (b, 2 * o, 2 * o, cin), (b, o, o, cout), "pallas"))
    ops.append(_dense("head", b, 16 * 2 * base, 1))
    return ops


# How many times one simultaneous GAN step needs each layer's product
# (a forward, an input gradient or a filter gradient each count once):
# the generator runs forward once (both losses share it), then its input
# and filter gradients; the discriminator runs forward on the fake and
# the real batch, its input gradients for the generator's loss, and its
# filter gradients plus the input gradients that reach them for its own
# loss on both batches (c1's input gradient is not needed there).
GAN_STEP_PASSES = {"proj": 2, "t1": 3, "t2": 3, "t3": 3,
                   "c1": 5, "c2": 7, "c3": 7, "head": 7}


def gan_step_ops(model: Dict, batch: int) -> List[Op]:
    """The useful work of one training step of the GAN at `batch`."""
    step = ops(model, batch) + discriminator_ops(model, batch)
    return [dataclasses.replace(o, flops=o.flops * GAN_STEP_PASSES[o.name],
                                bytes=o.bytes * GAN_STEP_PASSES[o.name])
            for o in step]
