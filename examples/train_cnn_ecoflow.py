"""End-to-end driver: train a CNN classifier with EcoFlow backward passes.

The paper's headline workload is CNN training on a spatial accelerator;
here every convolution's backward pass routes through the zero-free
transposed (input-grad) and dilated (filter-grad) dataflows.  Trains an
AllConvNet-style model (stride-2 convs instead of pooling -- the paper's
Sec. 6.1.1 optimization) on synthetic image data for a few hundred steps.

Run:  PYTHONPATH=src python examples/train_cnn_ecoflow.py [--steps 300]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import cnn
from repro.optim.optimizer import AdamWConfig, adamw_init, adamw_update
from repro.runtime import enable_compile_cache


def synth_batch(step: int, *, batch=32, size=24, n_classes=10):
    """Deterministic synthetic 'shapes' dataset: class = dominant stripe
    frequency -- learnable by a small CNN, pure function of step."""
    rng = np.random.default_rng(np.random.SeedSequence([7, step]))
    y = rng.integers(0, n_classes, batch)
    xs = []
    for i in range(batch):
        freq = 1 + y[i]
        t = np.linspace(0, np.pi * freq, size)
        img = np.outer(np.sin(t), np.cos(t))[..., None]
        img = np.repeat(img, 3, axis=-1)
        img += 0.35 * rng.standard_normal((size, size, 3))
        xs.append(img)
    return (jnp.asarray(np.stack(xs), jnp.float32),
            jnp.asarray(y, jnp.int32))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--backend", default="xla_zero_free",
                    choices=("reference", "xla_zero_free", "pallas"),
                    help="conv dispatch backend (repro.core.spec)")
    args = ap.parse_args()
    enable_compile_cache()

    params = cnn.simple_cnn_init(jax.random.PRNGKey(0),
                                 widths=(16, 32, 64), n_classes=10)
    ocfg = AdamWConfig(lr=args.lr, warmup_steps=20,
                       total_steps=args.steps, weight_decay=0.01)
    opt = adamw_init(params, ocfg)

    @jax.jit
    def step_fn(params, opt, x, y):
        loss, grads = jax.value_and_grad(
            lambda p: cnn.cnn_loss(p, x, y, stride=2,
                                   backend=args.backend))(params)
        params, opt, om = adamw_update(grads, opt, params, ocfg)
        acc = jnp.mean(
            jnp.argmax(cnn.simple_cnn_apply(params, x, stride=2,
                                            backend=args.backend), -1) == y)
        return params, opt, loss, acc

    t0 = time.time()
    for step in range(args.steps):
        x, y = synth_batch(step)
        params, opt, loss, acc = step_fn(params, opt, x, y)
        if step % 25 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(loss):.4f}  "
                  f"acc {float(acc):.2f}")
    dt = time.time() - t0
    print(f"\ntrained {args.steps} steps in {dt:.1f}s "
          f"({args.steps / dt:.1f} it/s); final train acc "
          f"{float(acc):.2f}")
    assert float(acc) > 0.5, "training should beat chance comfortably"


if __name__ == "__main__":
    main()
