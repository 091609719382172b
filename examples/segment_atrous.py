"""End-to-end driver: train the atrous segmentation head with zero-free
dilated-forward convolutions.

The segmentation-style workload the paper motivates (Sec. 1): DeepLab's
atrous convs apply the filter at rate D without losing resolution, and a
naive accelerator lowering schedules (D*(K-1)+1)^2 / K^2 more MACs than
useful.  Every branch here routes through `ecoflow_dilated_conv`, so the
dilated filter is never materialized -- forward or backward -- on any
backend.  The branch relu tails ride the declarative epilogue slot
(DESIGN Sec. 2.8): the head requests `Epilogue(activation="relu")` per
branch, so on the pallas backend each branch's forward AND backward stay
at one launch with the activation (and its gradient mask) fused in-VMEM.
`--no-fuse-epilogue` falls back to separate XLA relu ops for comparison.

Run:  PYTHONPATH=src python examples/segment_atrous.py [--steps 120]
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import vision
from repro.optim.optimizer import AdamWConfig, adamw_init, adamw_update
from repro.runtime import enable_compile_cache


def synth_batch(step: int, *, batch=8, size=24):
    """Deterministic synthetic segmentation set: each image carries a
    bright axis-aligned rectangle on textured noise; the per-pixel label
    is 1 inside the rectangle, else 0.  Pure function of `step`."""
    rng = np.random.default_rng(np.random.SeedSequence([11, step]))
    xs, ys = [], []
    for _ in range(batch):
        img = 0.3 * rng.standard_normal((size, size, 3))
        y = np.zeros((size, size), np.int32)
        r0, c0 = rng.integers(2, size - 10, 2)
        h, w = rng.integers(6, 10, 2)
        img[r0:r0 + h, c0:c0 + w] += 1.5
        y[r0:r0 + h, c0:c0 + w] = 1
        xs.append(img)
        ys.append(y)
    return (jnp.asarray(np.stack(xs), jnp.float32),
            jnp.asarray(np.stack(ys), jnp.int32))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=120)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--backend", default="xla_zero_free",
                    choices=("reference", "xla_zero_free", "pallas"),
                    help="conv dispatch backend (repro.core.spec)")
    ap.add_argument("--no-fuse-epilogue", dest="fuse_epilogue",
                    action="store_false",
                    help="run the branch relu tails as separate XLA ops "
                         "instead of the fused epilogue slot")
    args = ap.parse_args()
    enable_compile_cache()

    rates = (1, 2, 4)
    params = vision.atrous_head_init(jax.random.PRNGKey(0), in_ch=3,
                                     width=16, n_classes=2, rates=rates)
    ocfg = AdamWConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps,
                      weight_decay=0.01)
    opt = adamw_init(params, ocfg)

    @jax.jit
    def step_fn(params, opt, x, y):
        loss, grads = jax.value_and_grad(
            lambda p: vision.atrous_seg_loss(
                p, x, y, rates=rates, backend=args.backend,
                fuse_epilogue=args.fuse_epilogue))(params)
        params, opt, om = adamw_update(grads, opt, params, ocfg)
        logits = vision.atrous_head_apply(
            params, x, rates=rates, backend=args.backend,
            fuse_epilogue=args.fuse_epilogue)
        acc = jnp.mean(jnp.argmax(logits, -1) == y)
        return params, opt, loss, acc

    t0 = time.perf_counter()
    for step in range(args.steps):
        x, y = synth_batch(step)
        params, opt, loss, acc = step_fn(params, opt, x, y)
        if step % 20 == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(loss):.4f}  "
                  f"pixel-acc {float(acc):.3f}")
    dt = time.perf_counter() - t0
    print(f"done: {args.steps} steps in {dt:.1f}s "
          f"({1e3 * dt / args.steps:.1f} ms/step, backend={args.backend})")


if __name__ == "__main__":
    main()
