#!/usr/bin/env python3
"""On-chip smoke run of the EcoFlow conv stack (Pallas kernels, compiled).

    python chip_smoke.py              # one TPU chip: train + serve phases
    python chip_smoke.py --chips 4    # four chips: data-parallel GAN step
                                      # against the same steps on one chip

One chip:
  * train -- `ConvTrainer` runs 5 GAN steps (z_dim 64, base 64, 32x32
    images, batch 128) on the `pallas` backend, then the same 5 steps on
    the `reference` backend at highest matmul precision.  Every step must
    be finite (no guard event) and the per-step losses must agree; every
    `pallas_call` of the step must be compiled (none interpreted) and
    the compiled step must hold them as `tpu_custom_call`s.  A third run,
    pallas at highest precision, must also match the reference's
    parameter updates.
  * serve -- `ConvServeEngine` with the ladder cut to ("pallas",) serves
    `gan_gen` requests (the generator above) and `aspp` requests (the
    default atrous head, 128x128x3 images); no fallback, kernel fault or
    failure is allowed, and the outputs must match the reference backend.
Four chips: the same GAN `ConvTrainer` on a 4-way data-parallel mesh (the
`shard_map` conv dispatch with its psums) against the same steps on one
device, both at highest precision; losses and updates must agree and
every device must hold a B/4 batch shard.

Everything runs in this one process; nothing falls back.  Readings are
printed as `smoke:` lines -- they are smoke readings (one cold run, no
warm-up window), not benchmark numbers.  The last stdout line is
`{"ok": true, "device": {...}}`; any failed phase exits non-zero, and a
process with no TPU exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

# Tolerances.  The reference always runs at "highest" matmul precision.
# A pallas run at the default precision (what users run: single-pass
# bf16 products on the MXU, in the kernels and in XLA's dense layers) is
# held to the loss and served-output bounds; its parameter updates drift
# further over 5 steps (6.6e-2 on the generator projection in the first
# chip run), so updates are compared between runs at "highest" only.
LOSS_RTOL = 1e-2      # |loss - ref| / max(|ref|, 1) per step
UPDATE_RTOL = 5e-3    # ||dp - dp_ref|| / ||dp_ref|| per leaf, at highest
SERVE_RTOL = 2e-2     # max |out - ref| / max |ref| per request kind

STEPS = 5
GAN = dict(workload="gan", z_dim=64, base=64, image=32, batch=128,
           total_steps=STEPS, seed=0)


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def report(phase: str, **fields) -> None:
    print("smoke: " + json.dumps({"phase": phase, **fields}), flush=True)


def _walk(jaxpr):
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _walk(inner)


def _rel(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def _train(backend: str, mesh=None, *, precision=None):
    """5 guarded GAN steps through `ConvTrainer.run`: (history, stats,
    init state, final state, seconds)."""
    import jax
    from repro.train.conv_trainer import ConvTrainer, ConvTrainerConfig

    tr = ConvTrainer(ConvTrainerConfig(backend=backend, **GAN), mesh=mesh)
    init = tr.init_state()
    t0 = time.perf_counter()
    with (jax.default_matmul_precision(precision) if precision
          else contextlib.nullcontext()):
        out = tr.run()
    jax.block_until_ready(out["state"])
    return tr, out, init, time.perf_counter() - t0


def _compare_runs(phase: str, got, want, *, updates: bool) -> dict:
    """Per-step losses and per-leaf parameter updates of two runs; the
    updates are held to UPDATE_RTOL only when `updates` is set."""
    import jax
    import numpy as np
    (_, out, init, _), (_, out_r, init_r, _) = got, want
    losses = [h["loss"] for h in out["history"]]
    ref = [h["loss"] for h in out_r["history"]]
    loss_err = max(abs(a - b) / max(abs(b), 1.0) for a, b in zip(losses, ref))
    upd = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                       out["state"], init)
    upd_r = jax.tree.map(lambda a, b: np.asarray(a) - np.asarray(b),
                         out_r["state"], init_r)
    errs = {jax.tree_util.keystr(p): _rel(a, b) for (p, a), b in zip(
        jax.tree_util.tree_flatten_with_path(upd)[0],
        jax.tree.leaves(upd_r))}
    check(all(np.linalg.norm(u) > 0 for u in jax.tree.leaves(upd_r)),
          f"{phase}: a reference update is zero")
    check(loss_err <= LOSS_RTOL,
          f"{phase}: losses {losses} vs reference {ref} "
          f"(rel err {loss_err:.3e} > {LOSS_RTOL})")
    worst = max(errs, key=errs.get)
    check(not updates or errs[worst] <= UPDATE_RTOL,
          f"{phase}: update of {worst} off by {errs[worst]:.3e} "
          f"> {UPDATE_RTOL}; all leaves: {errs}; losses {losses} vs "
          f"reference {ref}")
    return {"losses": losses, "ref_losses": ref, "loss_rel_err": loss_err,
            "max_update_rel_err": errs[worst], "worst_leaf": worst}


def _check_clean(phase: str, out) -> None:
    stats = out["guard_stats"]
    check(len(out["history"]) == STEPS,
          f"{phase}: {len(out['history'])} of {STEPS} steps committed")
    check(all(stats[k] == 0 for k in ("nonfinite_steps", "retries",
                                      "skips", "give_ups")),
          f"{phase}: guard events {stats}")


def phase_train() -> None:
    import jax
    import jax.numpy as jnp

    from repro.train.conv_trainer import ConvTrainer, ConvTrainerConfig

    # Compile the step the run will execute first, for its HLO and a cold
    # compile time (the run then finds it in the compile cache).
    tr = ConvTrainer(ConvTrainerConfig(backend="pallas", **GAN))
    init = tr.init_state()
    data = tr._put_batch(tr.data.batch_at(0))
    lr = jnp.float32(tr.tcfg.lr)
    calls = [e for e in _walk(jax.make_jaxpr(tr.build_step(guarded=True))(
        init, data, lr).jaxpr) if e.primitive.name == "pallas_call"]
    t0 = time.perf_counter()
    compiled = tr._jit.lower(init, data, lr).compile()
    compile_s = time.perf_counter() - t0
    # XLA's CSE merges the launches the step repeats (the generator and
    # fake-image discriminator forwards appear in both losses), so the
    # compiled module may hold fewer custom calls than the jaxpr.
    n_compiled = compiled.as_text().count(
        'custom_call_target="tpu_custom_call"')
    check(calls and not any(e.params["interpret"] for e in calls),
          "train: the step has no compiled pallas_call")
    check(0 < n_compiled <= len(calls),
          f"train: {n_compiled} tpu_custom_calls in the compiled step for "
          f"{len(calls)} pallas_calls")

    got = _train("pallas")
    _, out, _, run_s = got
    _check_clean("train", out)
    t0 = time.perf_counter()
    for _ in range(3):
        jax.block_until_ready(compiled(init, data, lr))
    step_s = (time.perf_counter() - t0) / 3
    want = _train("reference", precision="highest")
    _check_clean("train-reference", want[1])
    cmp = _compare_runs("train", got, want, updates=False)
    report("train", backend="pallas", precision="default",
           steps=len(out["history"]), guard_stats=out["guard_stats"],
           pallas_calls=len(calls), tpu_custom_calls_compiled=n_compiled,
           step_compile_s=compile_s, step_s=step_s,
           run_s_incl_compile=run_s, reference_run_s=want[3], **cmp)
    hi = _train("pallas", precision="highest")
    _check_clean("train-highest", hi[1])
    cmp = _compare_runs("train-highest", hi, want, updates=True)
    report("train", backend="pallas", precision="highest",
           steps=len(hi[1]["history"]), run_s_incl_compile=hi[3], **cmp)


def phase_serve() -> None:
    import jax
    import numpy as np
    from repro.models import gan, vision
    from repro.serve.conv_engine import ConvRequest, ConvServeEngine

    key = jax.random.PRNGKey(1)
    g_params = gan.generator_init(key, z_dim=GAN["z_dim"], base=GAN["base"])
    aspp = vision.atrous_head_init(jax.random.fold_in(key, 1))
    eng = ConvServeEngine(gan_params=g_params, aspp_params=aspp,
                          slot_batch=4, ladder=("pallas",))
    shapes = [("gan_gen", (GAN["z_dim"],)), ("aspp", (128, 128, 3))]
    t0 = time.perf_counter()
    eng.warmup(shapes, compile=True)
    warm_s = time.perf_counter() - t0

    rng = np.random.default_rng(0)
    reqs = [ConvRequest(uid=i, kind=kind,
                        payload=rng.standard_normal(shape).astype(np.float32))
            for i, (kind, shape) in enumerate(shapes * 4)]
    t0 = time.perf_counter()
    res = eng.serve(reqs)
    serve_s = time.perf_counter() - t0
    h = eng.health()
    check(h["fallbacks"] == 0 and h["kernel_faults"] == 0
          and h["failures"] == 0,
          f"serve: fallbacks={h['fallbacks']} kernel_faults="
          f"{h['kernel_faults']} failures={h['failures']}")
    check(sorted(res) == [r.uid for r in reqs],
          f"serve: {len(res)} of {len(reqs)} requests answered")

    refs = {
        "gan_gen": jax.jit(lambda z: gan.generator_apply(
            g_params, z, backend="reference")),
        "aspp": jax.jit(lambda x: vision.atrous_head_apply(
            aspp, x, backend="reference")),
    }
    errs = {}
    for kind, fn in refs.items():
        mine = [r for r in reqs if r.kind == kind]
        with jax.default_matmul_precision("highest"):
            want = np.asarray(fn(np.stack([r.payload for r in mine])))
        got = np.stack([res[r.uid] for r in mine])
        check(got.shape == want.shape and np.all(np.isfinite(got)),
              f"serve {kind}: output {got.shape}, want {want.shape}")
        errs[kind] = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
        check(errs[kind] <= SERVE_RTOL,
              f"serve {kind}: max rel error {errs[kind]:.3e} > {SERVE_RTOL}")
    report("serve", requests=len(reqs), completed=h["completed"],
           launches=h["launches"], fallbacks=h["fallbacks"],
           kernel_faults=h["kernel_faults"], failures=h["failures"],
           max_rel_err=errs, warmup_compile_s=warm_s, serve_s=serve_s,
           p50_us=h["p50_us"], p99_us=h["p99_us"])


def phase_data_parallel(n: int) -> None:
    import jax
    import numpy as np
    from jax.sharding import Mesh

    mesh = Mesh(np.asarray(jax.devices()[:n]).reshape(n, 1),
                ("data", "model"))
    got = _train("pallas", mesh, precision="highest")
    tr, out, _, run_s = got
    _check_clean("dp", out)
    shards = [[(s.device.id, s.data.shape) for s in a.addressable_shards]
              for a in tr._put_batch(tr.data.batch_at(0))]
    for arr in shards:
        check(len({d for d, _ in arr}) == n
              and all(shape[0] == GAN["batch"] // n for _, shape in arr),
              f"dp: batch shards {arr}, want {n} x B/{n}")
    want = _train("pallas", precision="highest")
    _check_clean("dp-single", want[1])
    cmp = _compare_runs("dp", got, want, updates=True)
    report("data_parallel", devices=n, steps=len(out["history"]),
           batch_shards=[[s for _, s in arr] for arr in shards],
           run_s_incl_compile=run_s, single_device_run_s=want[3], **cmp)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}; run "
              f"from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.runtime import enable_compile_cache
    enable_compile_cache()
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{devices[0].platform!r}); this script runs on the chip "
              f"only", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    try:
        if args.chips == 1:
            phase_train()
            phase_serve()
        else:
            phase_data_parallel(args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
