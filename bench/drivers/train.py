"""Training: `ConvTrainer.run` on the GAN, steps back to back, guard on,
no checkpoints.  The trainer is built once and compiled; set-up drives
it from the seed through its first three steps by the window's own
`run` call, and the window then runs it again from the seed until
`--seconds` have passed, stopping through the `fail_hook(step)` seam.
Its first three steps must repeat the set-up's losses exactly, which
ties what the reference checks to what the window ran.

End-to-end: `train_throughput`, real images consumed per second (the
batch of every committed step over the window).
"""
from __future__ import annotations

import gc
import time
from typing import Dict

import jax
import numpy as np

from bench import flops, harness, reference

CHECK_STEPS = 3


class _Feed:
    """The trainer's data source: the benchmark's batches from the seed."""

    def __init__(self, mod, model: Dict, seed: int):
        self.mod, self.model, self.seed = mod, model, seed

    def batch_at(self, step: int) -> Dict[str, np.ndarray]:
        return self.mod.train_batch(self.model, self.seed, step)


def make_trainer(ctx: harness.Context, mesh=None):
    """The program's trainer over the benchmark's weights and feed."""
    from repro.parallel import sharding as sh
    from repro.train.conv_trainer import ConvTrainer, ConvTrainerConfig
    model, mod = ctx.config["model"], harness.model_of(ctx)

    class Trainer(ConvTrainer):
        def init_state(self):
            state = mod.gan_params(model, ctx.seed)
            if self.mesh is None:
                return state
            with self.mesh, sh.use_mesh(self.mesh):
                return jax.device_put(state,
                                      sh.tree_shardings(state, self.mesh))

    tcfg = ConvTrainerConfig(
        workload="gan", z_dim=model["z_dim"], base=model["base"],
        image=model["image"], channels=model["channels"],
        batch=model["batch"], backend="pallas", lr=ctx.cell["lr"],
        guard=True, total_steps=1)
    tr = Trainer(tcfg, mesh=mesh)
    tr.data = _Feed(mod, model, ctx.seed)
    return tr


def make_mesh(ctx: harness.Context):
    if len(ctx.devices) == 1:
        return None
    from jax.sharding import Mesh
    return Mesh(np.asarray(ctx.devices).reshape(len(ctx.devices), 1),
                ("data", "model"))


def _host(tree):
    return jax.tree.map(lambda a: np.asarray(a, np.float64), tree)


def _leaves(tree) -> Dict[str, np.ndarray]:
    return {jax.tree_util.keystr(p): v for p, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def norm_gap(got: Dict, want: Dict, counted) -> float:
    """Worst leaf's gap of norms: | |got| - |want| | over the larger of
    |want| and the median leaf's |want|."""
    norms = {k: float(np.linalg.norm(v)) for k, v in want.items()}
    med = float(np.median(list(norms.values())))
    return max(abs(float(np.linalg.norm(got[k])) - norms[k])
               / max(norms[k], med) for k in counted)


def compare(ctx: harness.Context, p0, p1, p3, losses) -> Dict[str, float]:
    """The timed path's first three steps against the reference's: each
    step's loss, the first gradient as SGD applied it ((p0 - p1) / lr)
    and the change of the weights over the three steps.  Leaves whose
    reference gradient is under a thousandth of the median leaf's move
    by round-off alone and are not counted."""
    model, mod = ctx.config["model"], harness.model_of(ctx)
    num = reference.numerics(ctx.config)
    lr = float(ctx.cell["lr"])
    step = jax.jit(lambda s, z, r: mod.gan_step(s, z, r, lr, num=num))
    state = mod.gan_params(model, ctx.seed)
    ref_losses, grads = [], None
    for k in range(CHECK_STEPS):
        b = mod.train_batch(model, ctx.seed, k)
        state, gl, _, g = step(state, b["z"], b["real"])
        ref_losses.append(float(gl))
        grads = grads or _leaves(_host(g))
    ref0 = _leaves(_host(mod.gan_params(model, ctx.seed)))
    ref3 = _leaves(_host(state))
    g_norm = {k: float(np.linalg.norm(v)) for k, v in grads.items()}
    med = float(np.median(list(g_norm.values())))
    counted = [k for k in grads if g_norm[k] >= 1e-3 * med]
    p0, p1, p3 = (_leaves(_host(p)) for p in (p0, p1, p3))
    return {
        "loss_err": max(abs(a - b) / abs(b)
                        for a, b in zip(losses, ref_losses)),
        "grad_err": norm_gap({k: (p0[k] - p1[k]) / lr for k in p0},
                             grads, counted),
        "change_err": norm_gap({k: p3[k] - p0[k] for k in p0},
                               {k: ref3[k] - ref0[k] for k in ref0},
                               counted),
    }


def run(ctx: harness.Context) -> harness.Outcome:
    model = ctx.config["model"]
    tr = make_trainer(ctx, make_mesh(ctx))
    out1 = tr.run()                       # compiles; one step
    tr.tcfg.total_steps = CHECK_STEPS
    out3 = tr.run()
    checked = [h["loss"] for h in out3["history"]]
    p0, p1, p3 = (jax.device_get(x) for x in (
        tr.init_state(), out1["state"], out3["state"]))
    skips0 = tr.guard.stats["skips"]
    t0 = [0.0]

    def hook(step: int) -> None:
        now = time.perf_counter()
        if step == 0:
            t0[0] = now
        elif now - t0[0] >= ctx.seconds:
            tr.tcfg.total_steps = step + 1    # this step is the last
    tr.tcfg.total_steps = 1 << 40
    setup_s = ctx.setup_s()
    with harness.profiled(ctx.trace, ctx.compiles) as prof:
        out = tr.run(fail_hook=hook)
        t_end = time.perf_counter() - t0[0]
    steps = len(out["history"])
    skipped = tr.guard.stats["skips"] - skips0
    mem = harness.memory_peak_bytes(ctx.devices)
    window_losses = [h["loss"] for h in out["history"][:CHECK_STEPS]]
    chips = len(ctx.devices)
    step_ops = harness.model_of(ctx).gan_step_ops(model, model["batch"])
    work = {"chips": chips, "peak_flops": ctx.peaks["flops_per_s"],
            "steps": steps, "useful_flops": steps * flops.total_flops(
                step_ops), "batch": model["batch"]}
    del tr, out, out1, out3
    gc.collect()
    errs = compare(ctx, p0, p1, p3, checked)
    repeat = float(np.max(np.abs(np.subtract(window_losses, checked))))
    limits = ctx.cell["check"]
    checks = {k: (v, float(limits[k])) for k, v in errs.items()}
    checks["window_repeats_setup"] = (repeat, 0.0)
    checks["skipped_steps"] = (skipped, 0)
    correct = all(v <= lim for v, lim in checks.values())
    return harness.Outcome(
        attempted=steps + skipped, failed=skipped,
        end_to_end={"train_throughput": steps * model["batch"] / t_end,
                    "setup_s": setup_s},
        checks=checks, correct=bool(correct), memory_peak_bytes=mem,
        window_s=t_end, layer=work, trace=prof.view)
