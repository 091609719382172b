"""GAN training example (the paper's Sec. 6.3 evaluation domain).

The DCGAN-style generator upsamples with the zero-free transposed-conv
dataflow (its forward pass IS the paper's input-gradient dataflow); the
discriminator downsamples with stride-2 convs whose backward pass uses the
zero-free dataflows.  Alternating non-saturating updates on synthetic
data.

Run:  PYTHONPATH=src python examples/train_gan.py [--steps 120]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import gan
from repro.optim.optimizer import AdamWConfig, adamw_init, adamw_update
from repro.runtime import enable_compile_cache


def real_batch(step, *, batch=16, size=32):
    """Synthetic 'real' distribution: smooth blobs (low-frequency)."""
    rng = np.random.default_rng(np.random.SeedSequence([11, step]))
    xy = np.linspace(-1, 1, size)
    gx, gy = np.meshgrid(xy, xy)
    imgs = []
    for _ in range(batch):
        cx, cy = rng.uniform(-0.5, 0.5, 2)
        s = rng.uniform(0.2, 0.5)
        img = np.exp(-((gx - cx) ** 2 + (gy - cy) ** 2) / s)[..., None]
        imgs.append(np.repeat(img, 3, axis=-1) * 2 - 1)
    return jnp.asarray(np.stack(imgs), jnp.float32)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--backend", default="xla_zero_free",
                    choices=("reference", "xla_zero_free", "pallas"),
                    help="conv dispatch backend (repro.core.spec)")
    args = ap.parse_args()
    enable_compile_cache()
    Z, BASE, B = 32, 16, 16

    gp = gan.generator_init(jax.random.PRNGKey(0), z_dim=Z, base=BASE)
    dp = gan.discriminator_init(jax.random.PRNGKey(1), base=BASE)
    gcfg = AdamWConfig(lr=2e-4, b1=0.5, warmup_steps=0,
                       total_steps=args.steps, weight_decay=0.0)
    dcfg = AdamWConfig(lr=2e-4, b1=0.5, warmup_steps=0,
                       total_steps=args.steps, weight_decay=0.0)
    g_opt, d_opt = adamw_init(gp, gcfg), adamw_init(dp, dcfg)

    @jax.jit
    def step_fn(gp, dp, g_opt, d_opt, z, real):
        be = args.backend
        d_loss, d_grads = jax.value_and_grad(
            lambda d: gan.gan_losses(gp, d, z, real, backend=be)[1])(dp)
        dp, d_opt, _ = adamw_update(d_grads, d_opt, dp, dcfg)
        g_loss, g_grads = jax.value_and_grad(
            lambda g: gan.gan_losses(g, dp, z, real, backend=be)[0])(gp)
        gp, g_opt, _ = adamw_update(g_grads, g_opt, gp, gcfg)
        return gp, dp, g_opt, d_opt, g_loss, d_loss

    t0 = time.time()
    for step in range(args.steps):
        rng = np.random.default_rng(np.random.SeedSequence([3, step]))
        z = jnp.asarray(rng.standard_normal((B, Z)), jnp.float32)
        real = real_batch(step, batch=B)
        gp, dp, g_opt, d_opt, gl, dl = step_fn(gp, dp, g_opt, d_opt, z,
                                               real)
        if step % 20 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  g_loss {float(gl):.3f}  "
                  f"d_loss {float(dl):.3f}")
    fake = gan.generator_apply(gp, z)
    print(f"\n{args.steps} alternating steps in {time.time() - t0:.1f}s; "
          f"generator output {fake.shape}, "
          f"range [{float(fake.min()):.2f}, {float(fake.max()):.2f}]")
    assert np.isfinite(float(gl)) and np.isfinite(float(dl))


if __name__ == "__main__":
    main()
