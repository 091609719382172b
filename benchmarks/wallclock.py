"""Wall-clock microbenchmarks: zero-free EcoFlow vs materialized-zero
naive dataflows, executed for real in JAX on this host (CPU here; the same
code paths compile for TPU) -- plus the conv *backend* comparison
(multi-launch `xla_zero_free` vs fused single-launch `pallas`) across the
paper's Table 5/7 layer geometries, the dilated-forward (atrous)
geometries at rates d in {2, 4}, the general strided+dilated
input-gradient geometries (S > 1 AND D > 1, the unified (phase, tap)
kernel's family), the FUSED dual-gradient backward (dx + dW from one
launch vs the two-launch pair it replaced), the EPILOGUE-fused families
(layer tails -- bias/activation forward, cotangent mask + db backward --
folded into the same launches vs the identical kernels with the tail as
separate XLA ops), and end-to-end TRAINING-STEP rows (a CNN SGD step and
a GAN generator step per backend, with and without fused epilogues --
the paper's headline numbers are training-step speedups, so the
trajectory file tracks the same quantity), emitted to BENCH_conv.json so
future PRs have a perf trajectory.

Reported as name,us_per_call,derived -- `derived` carries the speedup and
the useful-MAC fraction from the analytical model for cross-checking.
"""
from __future__ import annotations

import dataclasses
import functools
import json
import os
import pathlib
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ecoflow, naive
from repro.core.spec import ConvSpec, Epilogue, resolve_backend
from repro.kernels.ops import interpret_mode


def _time(fn, *args, iters=5, warmup=2):
    """MEDIAN per-call latency (us) over `iters` timed calls.  The
    median discards warm-outlier iterations (GC pauses, scheduler
    preemption, allocator warm-up that survives the warmup calls) that
    drag a mean upward, without under-reporting steady-state cost the
    way a min does on a frequency-drifting host -- keeping
    BENCH_conv.json rows comparable across PRs and autotune sweeps
    (`kernels/tiling.py` times candidates through this same helper)."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append(time.perf_counter() - t0)
    samples.sort()
    return samples[len(samples) // 2] * 1e6  # us


def _time_interleaved(fns, iters=5, warmup=1):
    """Median per-call latency (us) for several zero-arg callables,
    measured INTERLEAVED: each sweep times one call of every callable
    before the next sweep starts.  Sequential per-backend timing folds
    slow host drift (frequency scaling, co-tenant load) straight into
    the backend *comparison* -- interleaving gives every callable the
    same drift exposure, so the ratios BENCH_conv.json exists to track
    are stable even when absolute numbers wander."""
    for f in fns.values():
        for _ in range(warmup):
            jax.block_until_ready(f())
    samples = {k: [] for k in fns}
    for _ in range(iters):
        for k, f in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(f())
            samples[k].append(time.perf_counter() - t0)
    return {k: sorted(v)[len(v) // 2] * 1e6 for k, v in samples.items()}


# (name, N_err, K, S, Cin, Cout): error-map size, filter, stride, channels.
CASES = [
    ("resnet50-CONV3-like", 28, 3, 2, 32, 32),
    ("alexnet-CONV1-like", 28, 11, 4, 3, 16),
    ("gan-gen-like", 32, 4, 2, 32, 16),
    ("stride8-like", 16, 11, 8, 8, 8),
]


def run():
    rows = []
    rng = np.random.default_rng(0)
    for name, O, K, S, Ci, Co in CASES:
        B = 2
        dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
        N = S * (O - 1) + K
        x = jnp.asarray(rng.normal(size=(B, N, N, Ci)), jnp.float32)

        f_eco = jax.jit(lambda dy, w: ecoflow.transposed_conv_zero_free(
            dy, w, stride=(S, S), padding=(0, 0), n_out=(N, N)))
        f_nai = jax.jit(lambda dy, w: naive.transposed_conv_naive(
            dy, w, stride=(S, S), padding=(0, 0), n_out=(N, N)))
        np.testing.assert_allclose(np.asarray(f_eco(dy, w)),
                                   np.asarray(f_nai(dy, w)),
                                   rtol=1e-3, atol=1e-3)
        t_eco = _time(f_eco, dy, w)
        t_nai = _time(f_nai, dy, w)
        zf = ecoflow.tconv_zero_mac_fraction(O, K, S)
        rows.append((f"wallclock.tconv.ecoflow.{name}", round(t_eco, 1),
                     f"speedup={t_nai/t_eco:.2f}x;zero_frac={zf:.2f}"))
        rows.append((f"wallclock.tconv.naive.{name}", round(t_nai, 1), ""))

        g_eco = jax.jit(lambda x, dy:
                        ecoflow.dilated_conv_filter_grad_zero_free(
                            x, dy, stride=(S, S), padding=(0, 0), k=(K, K)))
        g_nai = jax.jit(lambda x, dy: naive.dilated_conv_filter_grad_naive(
            x, dy, stride=(S, S), padding=(0, 0), k=(K, K)))
        np.testing.assert_allclose(np.asarray(g_eco(x, dy)),
                                   np.asarray(g_nai(x, dy)),
                                   rtol=1e-2, atol=1e-2)
        t_eco = _time(g_eco, x, dy)
        t_nai = _time(g_nai, x, dy)
        rows.append((f"wallclock.filtergrad.ecoflow.{name}",
                     round(t_eco, 1), f"speedup={t_nai/t_eco:.2f}x"))
        rows.append((f"wallclock.filtergrad.naive.{name}",
                     round(t_nai, 1), ""))
    return rows


# ---------------------------------------------------------------------------
# Conv backend comparison: multi-launch xla_zero_free vs fused pallas
# ---------------------------------------------------------------------------

# Table 5/7 layer geometries (name, O, K, S, Ci, Co): filter/stride are the
# paper's; error-map spatial size and channels are capped so the
# interpret-mode Pallas path (CPU CI) finishes in seconds -- the phase
# structure (the thing the fused kernel changes) depends only on (K, S).
# On a real TPU the same code paths compile and the caps can be lifted.
CONV_BACKEND_CASES = [
    ("alexnet-CONV1",    14, 11, 4, 3, 16),
    ("resnet50-CONV3",   14, 3, 2, 32, 32),
    ("shufflenet-CONV2", 14, 3, 2, 29, 29),
    ("inception-CONV3",   8, 3, 2, 32, 32),
    ("alexnet-o-CONV1",   7, 11, 8, 3, 16),
    ("cyclegan-gen-TCONV1", 14, 3, 2, 32, 32),
    ("pix2pix-gen-TCONV4",  16, 4, 2, 32, 32),
]

BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent / \
    "BENCH_conv.json"

# Dilated-forward (atrous) geometries: DeepLab-ASPP-style 3x3 branches at
# rates d in {2, 4}, stride 1, same-padding (P = d) -- the dilated-forward
# workload class wired through the backends.  Spatial size / channels are
# capped for interpret-mode CI, like CONV_BACKEND_CASES above.
DILATED_FORWARD_CASES = [
    # (name, N, K, S, P, D, Ci, Co)
    ("deeplab-ASPP-d2", 17, 3, 1, 2, 2, 16, 16),
    ("deeplab-ASPP-d4", 17, 3, 1, 4, 4, 16, 16),
]

# General strided+dilated (S > 1 AND D > 1) input-gradient geometries --
# the conv family the unified (phase, tap) kernel runs in one launch
# (previously the multi-launch XLA scatter fallback on the `pallas`
# backend).  Sized for interpret-mode CI like the tables above.
STRIDED_DILATED_CASES = [
    # (name, O, K, S, P, D, Ci, Co)
    ("strided-atrous-s2d2", 10, 3, 2, 1, 2, 16, 16),
    ("strided-atrous-s3d2", 7, 3, 3, 1, 2, 16, 16),
]

# Epilogue-fusion families (DESIGN.md Sec. 2.8): the layer tail
# act(scale * conv + bias) folded into the fused launches.  Each direct
# case times the fused forward-with-epilogue and the fused
# backward-with-epilogue (mask + dx + dW + db from ONE launch) per
# backend, plus a `pallas_unfused` arm -- the same pallas kernels with
# the tail/mask/reduce as separate XLA ops -- so the fusion itself (not
# the kernel) is the measured quantity.  (name, O, K, S, Ci, Co, Epilogue).
EPILOGUE_CASES = [
    ("resnet50-CONV3-brelu", 14, 3, 2, 32, 32,
     Epilogue(activation="relu", bias=True)),
    ("dcgan-disc-leaky02", 14, 4, 2, 16, 32,
     Epilogue(activation="leaky_relu", slope=0.2)),
]

# Transposed-conv epilogue cases (GAN generator layer tails): fused
# tconv-with-epilogue forward and fused ct-backward (mask + ddy + dW +
# db from one launch).  (name, O, K, S, Ci, Co, Epilogue) -- Ci is the
# tconv OUTPUT side, where the bias rides.
TCONV_EPILOGUE_CASES = [
    ("dcgan-gen-TCONV2-brelu", 8, 4, 2, 16, 32,
     Epilogue(activation="relu", bias=True)),
    ("dcgan-gen-TCONV4-tanh", 16, 4, 2, 3, 16,
     Epilogue(activation="tanh")),
]

# End-to-end training-step cases: one full jit'd SGD step (forward +
# backward + update) through the real models, per backend -- the paper's
# headline metric.  `config` values stay JSON-round-trip stable (lists,
# ints) because the delta gate diffs them against the committed rows.
# The trailing flag is `fuse_epilogue`: the `-ep` variants request every
# layer tail (relu / leaky_relu / tanh) declaratively through the conv
# epilogue slot, so each layer's forward AND backward stay at one launch
# on the pallas backend (DESIGN.md Sec. 2.8).
TRAIN_STEP_CASES = [
    ("train-step-cnn", "cnn",
     {"widths": [8, 16], "batch": 2, "image": 12, "n_classes": 10}, False),
    ("train-step-cnn-ep", "cnn",
     {"widths": [8, 16], "batch": 2, "image": 12, "n_classes": 10}, True),
    ("train-step-gan-gen", "gan_gen",
     {"base": 8, "z_dim": 16, "batch": 2}, False),
    ("train-step-gan-gen-ep", "gan_gen",
     {"base": 8, "z_dim": 16, "batch": 2}, True),
]

# Multi-device training-step rows: the SAME interleaved-median train-step
# methodology executed on a ("data", "model") mesh of 1/2/4/8 forced
# host-platform devices.  Each device count runs in a SUBPROCESS (the XLA
# host device count is fixed when the backend initializes, so the parent
# cannot re-configure it per row); inside, `_train_step_fns(mesh=...)`
# shards params via the structural conv-filter rule and the batch via
# `batch_pspec`, and every conv launches through the shard_map dispatch
# layer (DESIGN.md Sec. 2.9).  Trailing list = device counts; batch 8 so
# the largest mesh still divides.  (name, kind, config, fuse, devices).
MULTIDEV_MESHES = {1: (1, 1), 2: (2, 1), 4: (2, 2), 8: (4, 2)}
MULTIDEV_TRAIN_CASES = [
    ("mdev-train-cnn-ep", "cnn",
     {"widths": [8, 16], "batch": 8, "image": 12, "n_classes": 10}, True,
     [1, 2, 4, 8]),
    ("mdev-train-gan-gen-ep", "gan_gen",
     {"base": 8, "z_dim": 16, "batch": 8}, True, [1, 8]),
]

# On interpret-mode hosts each fake device re-interprets its kernels, so
# the multidev rows cap their sweep count: the per-row median stabilizes
# well below this and the delta gate still compares like against like
# (the committed rows ran under the same cap).
_MULTIDEV_MAX_ITERS = 7

# Serving rows (DESIGN.md Sec. 2.11): the geometry-bucketed
# `ConvServeEngine` end to end -- admission queue, slot-batch assembly,
# jitted launch, host materialization -- per backend arm (each arm runs a
# single-rung ladder so the timing isolates the backend), reporting
# request p50/p99 latency and sustained requests/s.  Each case also runs
# a FAULT-MODE arm: the full degradation ladder under a seeded 5%
# kernel-fault schedule on the fast rungs, gated on bounded degradation
# (every admitted request completes; every fallback is accounted to an
# injected fault).  (name, kind, config).
SERVE_CASES = [
    ("serve-gan-gen", "gan_gen",
     {"z_dim": 16, "base": 8, "out_ch": 3, "slot_batch": 2,
      "requests": 8}),
    ("serve-aspp", "aspp",
     {"in_ch": 3, "width": 8, "n_classes": 4, "image": 8,
      "slot_batch": 2, "requests": 8}),
]
_SERVE_FAULT_RATE = 0.05
_SERVE_MAX_ITERS = 7    # interpret-mode cap, same rationale as multidev

# Elastic-training rows (DESIGN.md Sec. 2.12): two arms per case.
#   * `train_step_guard_us` -- the ConvTrainer's GUARDED jitted step
#     (in-graph all-finite flag over updated params + loss) vs the same
#     step unguarded, interleaved on the pallas backend; the
#     guarded/unguarded ratio is what the delta gate pins (the guard is
#     contractually cheap -- same launch count, a few XLA reductions).
#   * `recovery` -- a seeded supervisor drill in a SUBPROCESS with
#     `n_devices` forced host devices split over `hosts` hosts: the run
#     loses a host and hits injected NaN steps per the fixed
#     `fault_seed` (host losses from `host_failure_schedule`, NaN steps
#     from `faults.training_schedule` -- the same registry), and the
#     row records steps lost, recompiles, and recovery wallclock.  Run
#     once per bench (it is an accounting row, not a timing sweep); the
#     drill uses the xla_zero_free backend so the row measures the
#     recovery machinery, not interpret-mode kernel time.
ELASTIC_TRAIN_CASES = [
    ("elastic-train-cnn", "cnn",
     {"widths": [4], "batch": 8, "image": 8, "n_classes": 4,
      "total_steps": 8, "ckpt_every": 2, "backend": "xla_zero_free",
      "n_devices": 8, "hosts": 2, "fault_seed": 4,
      "host_rate": 0.12, "nan_rate": 0.2}),
    ("elastic-train-gan-gen", "gan_gen",
     {"base": 4, "z_dim": 8, "batch": 8,
      "total_steps": 8, "ckpt_every": 2, "backend": "xla_zero_free",
      "n_devices": 8, "hosts": 2, "fault_seed": 4,
      "host_rate": 0.12, "nan_rate": 0.2}),
]
_ELASTIC_MAX_ITERS = 7   # guard-arm cap, same rationale as multidev


def _serve_engine(kind, cfg, ladder, injector=None):
    """One `ConvServeEngine` for a serve bench arm, warmed up (tile
    plans + every ladder rung pre-compiled so the timed sweeps measure
    serving, not compilation).  Returns (engine, payload_shape)."""
    from repro.serve.conv_engine import ConvServeEngine
    if kind == "gan_gen":
        from repro.models import gan
        params = gan.generator_init(jax.random.PRNGKey(0),
                                    z_dim=cfg["z_dim"], base=cfg["base"],
                                    out_ch=cfg["out_ch"])
        eng = ConvServeEngine(gan_params=params,
                              slot_batch=cfg["slot_batch"],
                              queue_limit=max(64, cfg["requests"]),
                              ladder=ladder, injector=injector)
        payload_shape = (cfg["z_dim"],)
    elif kind == "aspp":
        from repro.models import vision
        params = vision.atrous_head_init(
            jax.random.PRNGKey(0), in_ch=cfg["in_ch"], width=cfg["width"],
            n_classes=cfg["n_classes"])
        eng = ConvServeEngine(aspp_params=params,
                              slot_batch=cfg["slot_batch"],
                              queue_limit=max(64, cfg["requests"]),
                              ladder=ladder, injector=injector)
        payload_shape = (cfg["image"], cfg["image"], cfg["in_ch"])
    else:
        raise ValueError(f"unknown serve kind {kind!r}")
    eng.warmup([(kind, payload_shape)])
    bucket = eng._bucket(kind, payload_shape)
    dummy = np.zeros((eng.slot_batch,) + payload_shape, np.float32)
    for rung in ladder:
        np.asarray(eng._jitted(bucket, rung)(dummy))
    return eng, payload_shape


def _multidev_measure(payload: dict) -> dict:
    """Subprocess body for one (case, device-count) multidev row: build
    the mesh from the forced host devices and time the interleaved
    backends.  Runs in a child with XLA_FLAGS set before jax init."""
    shape = tuple(payload["mesh_shape"])
    devs = np.asarray(jax.devices()[:shape[0] * shape[1]]).reshape(shape)
    mesh = jax.sharding.Mesh(devs, ("data", "model"))
    fns = _train_step_fns(payload["kind"], payload["config"],
                          tuple(payload["backends"]),
                          np.random.default_rng(0),
                          fuse_epilogue=payload["fuse"], mesh=mesh)
    return _time_interleaved(fns, iters=payload["iters"],
                             warmup=payload["warmup"])


def _multidev_time(kind, cfg, fuse, n_devices, iters, warmup,
                   backends=("xla_zero_free", "pallas")) -> dict:
    """Run `_multidev_measure` in a subprocess with the host device count
    forced to `n_devices`; returns {backend: us}."""
    payload = json.dumps({
        "kind": kind, "config": cfg, "fuse": fuse,
        "mesh_shape": list(MULTIDEV_MESHES[n_devices]),
        "backends": list(backends),
        "iters": min(iters, _MULTIDEV_MAX_ITERS), "warmup": warmup})
    root = BENCH_JSON.parent
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count"
                          f"={n_devices}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [str(root / "src"), str(root),
                    env.get("PYTHONPATH", "")] if p)
    code = ("import sys, json\n"
            "from benchmarks.wallclock import _multidev_measure\n"
            "print(json.dumps(_multidev_measure("
            "json.loads(sys.stdin.read()))))\n")
    proc = subprocess.run([sys.executable, "-c", code], input=payload,
                          capture_output=True, text=True, cwd=str(root),
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(
            f"multidev bench child (devices={n_devices}, kind={kind}) "
            f"failed:\n{proc.stdout}\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _train_step_fns(kind, cfg, backends, rng, fuse_epilogue=False,
                    mesh=None):
    """Zero-arg jit'd SGD-step callables per backend for one train-step
    case: forward + backward (the FUSED dual-gradient launch on the
    pallas backend) + parameter update through the models' own step
    helpers (`cnn.sgd_step` / `gan.gen_sgd_step`), on shared params/data
    so the interleaved timing compares backends on identical work.

    `mesh` (a jax Mesh) runs the step multi-device: params are
    device_put against `sharding.tree_shardings` (conv filters carry the
    structural 4-D (.., Cin@fsdp, Cout@tp) rule), the batch against
    `sharding.batch_pspec`, and both tracing and execution happen under
    `sharding.use_mesh` so every conv dispatches to a shard_map'd launch
    (DESIGN.md Sec. 2.9)."""
    lr = 0.05
    if kind == "cnn":
        from repro.models import cnn
        params = cnn.simple_cnn_init(jax.random.PRNGKey(0), in_ch=3,
                                     widths=tuple(cfg["widths"]),
                                     n_classes=cfg["n_classes"])
        x = jnp.asarray(rng.normal(size=(cfg["batch"], cfg["image"],
                                         cfg["image"], 3)), jnp.float32)
        labels = jnp.asarray(rng.integers(0, cfg["n_classes"],
                                          size=cfg["batch"]))
        data = (x, labels)

        def step_of(be):
            def step(p, d):
                return cnn.sgd_step(p, d[0], d[1], lr=lr, stride=2,
                                    backend=be,
                                    fuse_epilogue=fuse_epilogue)[0]
            return step
    elif kind == "gan_gen":
        from repro.models import gan
        k1, k2 = jax.random.split(jax.random.PRNGKey(0))
        params = gan.generator_init(k1, z_dim=cfg["z_dim"],
                                    base=cfg["base"], out_ch=3)
        d_params = gan.discriminator_init(k2, in_ch=3, base=cfg["base"])
        z = jnp.asarray(rng.normal(size=(cfg["batch"], cfg["z_dim"])),
                        jnp.float32)
        data = (z,)

        def step_of(be):
            def step(p, d):
                return gan.gen_sgd_step(p, d_params, d[0], lr=lr,
                                        backend=be,
                                        fuse_epilogue=fuse_epilogue)[0]
            return step
    else:
        raise ValueError(f"unknown train-step kind {kind!r}")

    if mesh is not None:
        from jax.sharding import NamedSharding
        from repro.parallel import sharding as sh
        with mesh, sh.use_mesh(mesh):
            params = jax.device_put(params, sh.tree_shardings(params, mesh))
            data = tuple(jax.device_put(d, NamedSharding(
                mesh, sh.batch_pspec(mesh, d.ndim, 0, d.shape[0])))
                for d in data)
    fns = {}
    for bname in backends:
        f = jax.jit(step_of(bname))
        if mesh is None:
            fns[bname] = lambda f=f, p=params, d=data: f(p, d)
        else:
            def call(f=f, p=params, d=data, m=mesh):
                from repro.parallel import sharding as sh
                with m, sh.use_mesh(m):
                    return f(p, d)
            fns[bname] = call
    return fns


# ConvTrainerConfig fields an elastic bench config may carry; the rest
# of the config dict (n_devices, hosts, fault_seed, ...) is drill-level.
_ELASTIC_TRAINER_KEYS = ("widths", "image", "channels", "n_classes",
                         "z_dim", "base", "batch", "total_steps", "lr",
                         "stride", "ckpt_every", "backend")


def _elastic_trainer_cfg(kind, cfg, **overrides):
    from repro.train.conv_trainer import ConvTrainerConfig
    kw = {k: cfg[k] for k in _ELASTIC_TRAINER_KEYS if k in cfg}
    if "widths" in kw:
        kw["widths"] = tuple(kw["widths"])
    kw.update(overrides)
    return ConvTrainerConfig(workload=kind, fuse_epilogue=True, **kw)


def _guard_step_fns(kind, cfg):
    """Zero-arg jitted callables for the guarded vs unguarded
    ConvTrainer step on the pallas backend, shared state/batch --
    the interleaved pair behind `train_step_guard_us`."""
    from repro.train.conv_trainer import ConvTrainer
    tcfg = _elastic_trainer_cfg(kind, cfg, backend="pallas",
                                ckpt_dir=None)
    trainer = ConvTrainer(tcfg)
    state = trainer.init_state()
    data = trainer._put_batch(trainer.data.batch_at(0))
    lr = np.float32(tcfg.lr)
    fns = {}
    for label, guarded in (("pallas", True), ("pallas_unguarded", False)):
        f = jax.jit(trainer.build_step(guarded=guarded))
        fns[label] = lambda f=f: f(state, data, lr)
    return fns


def _elastic_recovery_measure(payload: dict) -> dict:
    """Subprocess body for one elastic-recovery drill: run the
    RunSupervisor storm (seeded host loss + seeded NaN steps) on the
    forced host devices and report the recovery accounting."""
    import tempfile
    from repro.serve.faults import FaultInjector, training_schedule
    from repro.train.fault_tolerance import host_failure_schedule
    from repro.train.supervisor import RunSupervisor
    kind, cfg = payload["kind"], payload["config"]
    n_dev, hosts = cfg["n_devices"], cfg["hosts"]
    host_sched = host_failure_schedule(
        cfg["fault_seed"], n_hosts=hosts, n_steps=cfg["total_steps"],
        rate=cfg["host_rate"])
    inj = FaultInjector(training_schedule(
        cfg["fault_seed"], workload=kind, n_steps=4 * cfg["total_steps"],
        rate=cfg["nan_rate"], kinds=("nan_output",)))
    with tempfile.TemporaryDirectory() as d:
        tcfg = _elastic_trainer_cfg(kind, cfg, ckpt_dir=d)
        sup = RunSupervisor(tcfg, devices_per_host=n_dev // hosts,
                            model_parallel=2, host_schedule=host_sched,
                            injector=inj)
        t0 = time.perf_counter()
        out = sup.run()
        wall = time.perf_counter() - t0
    rep = out["report"]
    return {"steps_lost": rep["steps_lost"],
            "recompiles": rep["recompiles"],
            "recovery_wallclock_s": round(rep["recovery_wallclock_s"], 3),
            "host_losses": rep["host_losses"],
            "nonfinite_steps": rep["guard"]["nonfinite_steps"],
            "meshes": rep["meshes"],
            "completed_steps": (out["history"][-1]["step"]
                                if out["history"] else 0),
            "drill_wall_s": round(wall, 3)}


def _elastic_recovery(kind, cfg) -> dict:
    """Run `_elastic_recovery_measure` in a subprocess with the host
    device count forced to the case's `n_devices` (same launcher
    pattern as `_multidev_time`)."""
    payload = json.dumps({"kind": kind, "config": cfg})
    root = BENCH_JSON.parent
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count"
                          f"={cfg['n_devices']}").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in [str(root / "src"), str(root),
                    env.get("PYTHONPATH", "")] if p)
    code = ("import sys, json\n"
            "from benchmarks.wallclock import _elastic_recovery_measure\n"
            "print(json.dumps(_elastic_recovery_measure("
            "json.loads(sys.stdin.read()))))\n")
    proc = subprocess.run([sys.executable, "-c", code], input=payload,
                          capture_output=True, text=True, cwd=str(root),
                          env=env)
    if proc.returncode != 0:
        raise RuntimeError(
            f"elastic recovery drill child (kind={kind}) failed:\n"
            f"{proc.stdout}\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _plan_dict(op, spec, x_shape, dy_shape, epilogue=None):
    """The planner's decision for one (op, geometry) -- recorded per
    BENCH_conv.json row so the perf trajectory is attributable to the
    tiling AND the kernel strategy that produced it (`strategy` is the
    `plan_strategy` pick; "phase" for every op implicit-GEMM does not
    cover)."""
    from repro.kernels import tiling
    strategy, plan = tiling.plan_strategy(
        op, spec, x_shape=x_shape, dy_shape=dy_shape,
        interpret=interpret_mode(), epilogue=epilogue)
    return {"cin_tile": plan.cin_tile, "cout_tile": plan.cout_tile,
            "spatial_tile": plan.spatial_tile,
            "tap_unroll": plan.tap_unroll,
            "phase_unroll": plan.phase_unroll, "source": plan.source,
            "strategy": strategy}


def _race_input_grad(dy, w, spec, n_out, bias=None, epilogue=None,
                     iters=5, warmup=1):
    """Time the input gradient under BOTH forced kernel strategies
    (interleaved, same methodology as the backend arms) and name the
    measured winner -- the per-geometry ground truth the planner's
    `strategy` pick is judged against in BENCH_conv.json."""
    from repro.kernels import ops as kops
    fns = {}
    for strategy in ("phase", "implicit_gemm"):
        f = jax.jit(functools.partial(
            kops.tconv_phase, stride=spec.stride, padding=spec.padding,
            n_out=n_out, dilation=spec.dilation, epilogue=epilogue,
            strategy=strategy))
        if bias is None:
            fns[strategy] = lambda f=f: f(dy, w)
        else:
            fns[strategy] = lambda f=f: f(dy, w, bias=bias)
    t = _time_interleaved(fns, iters=iters, warmup=warmup)
    return ({k: round(v, 1) for k, v in t.items()},
            min(t, key=t.get))


def conv_backend_bench(iters=5, warmup=1, write_json=True, cases=None,
                       dilated_cases=None, strided_dilated_cases=None,
                       train_cases=None, epilogue_cases=None,
                       tconv_epilogue_cases=None, multidev_cases=None,
                       serve_cases=None, elastic_cases=None,
                       json_path=None, name_filter=None,
                       records_out=None):
    """Time tconv + filter-grad + the FUSED dual-gradient backward
    through the xla_zero_free and pallas backends for each geometry --
    plus the dilated-forward conv (d in {2, 4}), the general
    strided+dilated input gradient, and end-to-end TRAINING-STEP rows
    (CNN SGD step, GAN generator step) through the same backends (and,
    for the dilated forward, the materialized-filter naive baseline);
    write BENCH_conv.json and return CSV rows.  The backward rows carry a
    third timing, `two_launch`: the pallas input_grad + filter_grad pair
    the fused kernel replaced, timed in the same interleaved sweep -- the
    fused/two-launch ratio is the quantity the delta gate pins.  The
    EPILOGUE families time the same workloads with the layer tail (bias
    / activation / cotangent mask / db reduce) fused into the launches,
    against a `pallas_unfused` arm that runs the identical pallas
    kernels with the tail as separate XLA ops -- isolating the fusion
    itself.  The MULTIDEV family re-times the train-step rows on meshes
    of 1/2/4/8 forced host-platform devices through the shard_map conv
    dispatch layer (DESIGN.md Sec. 2.9), one subprocess per device count
    (`_multidev_time`).  `cases`/`dilated_cases`/
    `strided_dilated_cases`/`train_cases`/`epilogue_cases`/
    `tconv_epilogue_cases`/`multidev_cases`/`json_path`
    exist for the CI smoke run (one tiny geometry per family).  `name_filter` (case-name substring) reruns single rows
    cheaply during autotuning -- a filtered run never writes
    BENCH_conv.json (it would drop the unselected rows).  `records_out`,
    if a list, receives the per-case record dicts (the delta gate
    consumes them).
    """
    rows, records = [], []
    if name_filter is not None:
        write_json = False
        flt = lambda cs: [c for c in cs if name_filter in c[0]]
    else:
        flt = lambda cs: cs
    rng = np.random.default_rng(0)
    backends = ("xla_zero_free", "pallas")
    for name, O, K, S, Ci, Co in flt(CONV_BACKEND_CASES if cases is None
                                     else cases):
        B, P = 1, 0
        spec = ConvSpec.make(stride=S, padding=P, filter_shape=K)
        N = spec.input_size((O, O))[0]
        dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
        x = jnp.asarray(rng.normal(size=(B, N, N, Ci)), jnp.float32)
        rec = {"layer": name, "error_map": O, "k": K, "stride": S,
               "c_in": Ci, "c_out": Co, "batch": B,
               "interpret_mode": interpret_mode(),
               "epilogue": "none",
               "tiling": {
                   "input_grad": _plan_dict("input_grad", spec,
                                            x.shape, dy.shape),
                   "filter_grad": _plan_dict("filter_grad", spec,
                                             x.shape, dy.shape),
                   "backward": _plan_dict("backward", spec,
                                          x.shape, dy.shape)},
               "tconv_us": {}, "filter_grad_us": {}, "backward_us": {}}
        # The planner's strategy pick for this geometry's input gradient,
        # plus the measured per-strategy race it is judged against.
        rec["strategy"] = rec["tiling"]["input_grad"]["strategy"]
        race_us, rec["winner"] = _race_input_grad(
            dy, w, spec, (N, N), iters=iters, warmup=warmup)
        for strategy, us in race_us.items():
            rec["tconv_us"][f"pallas_{strategy}"] = us
            rows.append((f"wallclock.tconv.pallas_{strategy}.{name}",
                         us, f"winner={rec['winner']}"))
        fns_t, fns_g, fns_b = {}, {}, {}
        for bname in backends:
            be = resolve_backend(bname)
            f_t = jax.jit(lambda dy_, w_, be=be: be.input_grad(
                dy_, w_, spec, (N, N)))
            f_g = jax.jit(lambda x_, dy_, be=be: be.filter_grad(
                x_, dy_, spec))
            f_b = jax.jit(lambda x_, dy_, w_, be=be: be.backward(
                x_, dy_, w_, spec, (N, N)))
            fns_t[bname] = lambda f=f_t: f(dy, w)
            fns_g[bname] = lambda f=f_g: f(x, dy)
            fns_b[bname] = lambda f=f_b: f(x, dy, w)
        # The two-launch pair the fused backward replaced, on the SAME
        # pallas kernels, timed in the same interleaved sweep.
        be_pl = resolve_backend("pallas")
        f_two = jax.jit(lambda x_, dy_, w_: (
            be_pl.input_grad(dy_, w_, spec, (N, N)),
            be_pl.filter_grad(x_, dy_, spec)))
        fns_b["two_launch"] = lambda: f_two(x, dy, w)
        t_t = _time_interleaved(fns_t, iters=iters, warmup=warmup)
        t_g = _time_interleaved(fns_g, iters=iters, warmup=warmup)
        t_b = _time_interleaved(fns_b, iters=iters, warmup=warmup)
        for bname in backends:
            rec["tconv_us"][bname] = round(t_t[bname], 1)
            rec["filter_grad_us"][bname] = round(t_g[bname], 1)
            rows.append((f"wallclock.tconv.{bname}.{name}",
                         round(t_t[bname], 1), ""))
            rows.append((f"wallclock.filtergrad.{bname}.{name}",
                         round(t_g[bname], 1), ""))
        for bname in list(backends) + ["two_launch"]:
            rec["backward_us"][bname] = round(t_b[bname], 1)
            derived = "" if bname != "pallas" else (
                f"fused_vs_two_launch="
                f"{t_b['two_launch'] / t_b['pallas']:.2f}x")
            rows.append((f"wallclock.backward.{bname}.{name}",
                         round(t_b[bname], 1), derived))
        records.append(rec)
    for name, N, K, S, P, D, Ci, Co in flt(DILATED_FORWARD_CASES
                                           if dilated_cases is None
                                           else dilated_cases):
        B = 1
        spec = ConvSpec.make(stride=S, padding=P, filter_shape=K,
                             dilation=D)
        x = jnp.asarray(rng.normal(size=(B, N, N, Ci)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
        Oh, Ow = spec.out_size((N, N))
        zf = naive.dilated_forward_zero_mac_fraction(K, D)
        rec = {"layer": name, "n_in": N, "k": K, "stride": S,
               "dilation": D, "c_in": Ci, "c_out": Co, "batch": B,
               "interpret_mode": interpret_mode(),
               "zero_mac_fraction_naive": round(zf, 4),
               "epilogue": "none",
               "tiling": {
                   "forward": _plan_dict("forward", spec, x.shape,
                                         (B, Oh, Ow, Co))},
               "dilated_forward_us": {}}
        rec["strategy"] = rec["tiling"]["forward"]["strategy"]
        f_nai = jax.jit(lambda x_, w_: naive.dilated_forward_naive(
            x_, w_, stride=S, padding=P, dilation=D))
        fns_d = {"naive_materialized": lambda: f_nai(x, w)}
        for bname in backends:
            be = resolve_backend(bname)
            f_d = jax.jit(lambda x_, w_, be=be: be.forward(x_, w_, spec))
            np.testing.assert_allclose(np.asarray(f_d(x, w)),
                                       np.asarray(f_nai(x, w)),
                                       rtol=1e-3, atol=1e-3)
            fns_d[bname] = lambda f=f_d: f(x, w)
        t_d = _time_interleaved(fns_d, iters=iters, warmup=warmup)
        t_nai = t_d["naive_materialized"]
        rec["dilated_forward_us"]["naive_materialized"] = round(t_nai, 1)
        rows.append((f"wallclock.dilated_forward.naive.{name}",
                     round(t_nai, 1), f"zero_frac={zf:.2f}"))
        for bname in backends:
            rec["dilated_forward_us"][bname] = round(t_d[bname], 1)
            rows.append((f"wallclock.dilated_forward.{bname}.{name}",
                         round(t_d[bname], 1),
                         f"speedup_vs_naive={t_nai/t_d[bname]:.2f}x"))
        records.append(rec)
    for name, O, K, S, P, D, Ci, Co in flt(STRIDED_DILATED_CASES
                                           if strided_dilated_cases is None
                                           else strided_dilated_cases):
        B = 2
        spec = ConvSpec.make(stride=S, padding=P, filter_shape=K,
                             dilation=D)
        n_out = spec.input_size((O, O))
        dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
        rec = {"layer": name, "error_map": O, "k": K, "stride": S,
               "dilation": D, "c_in": Ci, "c_out": Co, "batch": B,
               "interpret_mode": interpret_mode(),
               "epilogue": "none",
               "tiling": {
                   "input_grad": _plan_dict(
                       "input_grad", spec,
                       (B, n_out[0], n_out[1], Ci), dy.shape)},
               "input_grad_us": {}}
        rec["strategy"] = rec["tiling"]["input_grad"]["strategy"]
        race_us, rec["winner"] = _race_input_grad(
            dy, w, spec, n_out, iters=iters, warmup=warmup)
        for strategy, us in race_us.items():
            rec["input_grad_us"][f"pallas_{strategy}"] = us
            rows.append((f"wallclock.input_grad.pallas_{strategy}.{name}",
                         us, f"winner={rec['winner']}"))
        outs, fns_i = {}, {}
        for bname in backends:
            be = resolve_backend(bname)
            f_i = jax.jit(lambda dy_, w_, be=be: be.input_grad(
                dy_, w_, spec, n_out))
            outs[bname] = np.asarray(f_i(dy, w))
            fns_i[bname] = lambda f=f_i: f(dy, w)
        t_i = _time_interleaved(fns_i, iters=iters, warmup=warmup)
        for bname in backends:
            rec["input_grad_us"][bname] = round(t_i[bname], 1)
            rows.append((f"wallclock.input_grad.{bname}.{name}",
                         round(t_i[bname], 1), ""))
        np.testing.assert_allclose(outs["pallas"], outs["xla_zero_free"],
                                   rtol=1e-3, atol=1e-3)
        records.append(rec)
    for name, O, K, S, Ci, Co, ep in flt(EPILOGUE_CASES
                                         if epilogue_cases is None
                                         else epilogue_cases):
        B, P = 1, 0
        spec = ConvSpec.make(stride=S, padding=P, filter_shape=K)
        N = spec.input_size((O, O))[0]
        x = jnp.asarray(rng.normal(size=(B, N, N, Ci)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
        b = (jnp.asarray(rng.normal(size=(Co,)), jnp.float32)
             if ep.bias else None)
        dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
        rec = {"layer": name, "error_map": O, "k": K, "stride": S,
               "c_in": Ci, "c_out": Co, "batch": B,
               "interpret_mode": interpret_mode(),
               "epilogue": ep.tag,
               "tiling": {
                   "forward": _plan_dict("forward", spec, x.shape,
                                         dy.shape, epilogue=ep),
                   "backward": _plan_dict("backward", spec, x.shape,
                                          dy.shape, epilogue=ep)},
               "forward_ep_us": {}, "backward_ep_us": {}}
        # Fused dual-gradient backward: phase-decomposed by design.
        rec["strategy"] = rec["tiling"]["backward"]["strategy"]
        fns_f, fns_b, ys = {}, {}, {}
        for bname in backends:
            be = resolve_backend(bname)
            f_f = jax.jit(lambda x_, w_, b_, be=be: be.forward_ep(
                x_, w_, b_, spec, ep))
            ys[bname] = f_f(x, w, b)
            f_b = jax.jit(lambda x_, y_, dy_, w_, be=be: be.backward_ep(
                x_, y_, dy_, w_, spec, (N, N), ep))
            fns_f[bname] = lambda f=f_f: f(x, w, b)
            fns_b[bname] = lambda f=f_b, y=ys[bname]: f(x, y, dy, w)
        np.testing.assert_allclose(np.asarray(ys["pallas"]),
                                   np.asarray(ys["xla_zero_free"]),
                                   rtol=1e-3, atol=1e-3)
        # The tail as separate XLA ops around the SAME backend kernels:
        # clearing the fused slots drops ConvBackend onto its generic
        # mask/db-reduce composition, so this arm isolates the fusion.
        be_unf = dataclasses.replace(resolve_backend("pallas"),
                                     fused_forward_ep=None,
                                     fused_backward_ep=None)
        f_f_unf = jax.jit(lambda x_, w_, b_: be_unf.forward_ep(
            x_, w_, b_, spec, ep))
        f_b_unf = jax.jit(lambda x_, y_, dy_, w_: be_unf.backward_ep(
            x_, y_, dy_, w_, spec, (N, N), ep))
        fns_f["pallas_unfused"] = lambda: f_f_unf(x, w, b)
        fns_b["pallas_unfused"] = lambda: f_b_unf(x, ys["pallas"], dy, w)
        t_f = _time_interleaved(fns_f, iters=iters, warmup=warmup)
        t_b = _time_interleaved(fns_b, iters=iters, warmup=warmup)
        for bname in list(backends) + ["pallas_unfused"]:
            rec["forward_ep_us"][bname] = round(t_f[bname], 1)
            rec["backward_ep_us"][bname] = round(t_b[bname], 1)
            derived = "" if bname != "pallas" else (
                f"fused_vs_unfused="
                f"{t_b['pallas_unfused'] / t_b['pallas']:.2f}x")
            rows.append((f"wallclock.forward_ep.{bname}.{name}",
                         round(t_f[bname], 1), ""))
            rows.append((f"wallclock.backward_ep.{bname}.{name}",
                         round(t_b[bname], 1), derived))
        records.append(rec)
    for name, O, K, S, Ci, Co, ep in flt(TCONV_EPILOGUE_CASES
                                         if tconv_epilogue_cases is None
                                         else tconv_epilogue_cases):
        B, P = 1, 0
        spec = ConvSpec.make(stride=S, padding=P, filter_shape=K)
        n_out = spec.input_size((O, O))
        dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
        b = (jnp.asarray(rng.normal(size=(Ci,)), jnp.float32)
             if ep.bias else None)
        g_shape = (B, n_out[0], n_out[1], Ci)
        g = jnp.asarray(rng.normal(size=g_shape), jnp.float32)
        rec = {"layer": name, "error_map": O, "k": K, "stride": S,
               "c_in": Ci, "c_out": Co, "batch": B,
               "interpret_mode": interpret_mode(),
               "epilogue": ep.tag,
               "tiling": {
                   "input_grad": _plan_dict("input_grad", spec, g_shape,
                                            dy.shape, epilogue=ep),
                   "ct_backward": _plan_dict("ct_backward", spec, g_shape,
                                             dy.shape, epilogue=ep)},
               "tconv_ep_us": {}, "ct_backward_ep_us": {}}
        rec["strategy"] = rec["tiling"]["input_grad"]["strategy"]
        race_us, rec["winner"] = _race_input_grad(
            dy, w, spec, n_out, bias=b, epilogue=ep,
            iters=iters, warmup=warmup)
        for strategy, us in race_us.items():
            rec["tconv_ep_us"][f"pallas_{strategy}"] = us
            rows.append((f"wallclock.tconv_ep.pallas_{strategy}.{name}",
                         us, f"winner={rec['winner']}"))
        fns_t, fns_c, zs = {}, {}, {}
        for bname in backends:
            be = resolve_backend(bname)
            f_t = jax.jit(lambda dy_, w_, b_, be=be: be.input_grad_ep(
                dy_, w_, b_, spec, n_out, ep))
            zs[bname] = f_t(dy, w, b)
            f_c = jax.jit(lambda g_, z_, dy_, w_, be=be:
                          be.ct_backward_ep(g_, z_, dy_, w_, spec, ep))
            fns_t[bname] = lambda f=f_t: f(dy, w, b)
            fns_c[bname] = lambda f=f_c, z=zs[bname]: f(g, z, dy, w)
        np.testing.assert_allclose(np.asarray(zs["pallas"]),
                                   np.asarray(zs["xla_zero_free"]),
                                   rtol=1e-3, atol=1e-3)
        be_unf = dataclasses.replace(resolve_backend("pallas"),
                                     fused_input_grad_ep=None,
                                     fused_ct_backward_ep=None)
        f_t_unf = jax.jit(lambda dy_, w_, b_: be_unf.input_grad_ep(
            dy_, w_, b_, spec, n_out, ep))
        f_c_unf = jax.jit(lambda g_, z_, dy_, w_: be_unf.ct_backward_ep(
            g_, z_, dy_, w_, spec, ep))
        fns_t["pallas_unfused"] = lambda: f_t_unf(dy, w, b)
        fns_c["pallas_unfused"] = lambda: f_c_unf(g, zs["pallas"], dy, w)
        t_t = _time_interleaved(fns_t, iters=iters, warmup=warmup)
        t_c = _time_interleaved(fns_c, iters=iters, warmup=warmup)
        for bname in list(backends) + ["pallas_unfused"]:
            rec["tconv_ep_us"][bname] = round(t_t[bname], 1)
            rec["ct_backward_ep_us"][bname] = round(t_c[bname], 1)
            derived = "" if bname != "pallas" else (
                f"fused_vs_unfused="
                f"{t_c['pallas_unfused'] / t_c['pallas']:.2f}x")
            rows.append((f"wallclock.tconv_ep.{bname}.{name}",
                         round(t_t[bname], 1), ""))
            rows.append((f"wallclock.ct_backward_ep.{bname}.{name}",
                         round(t_c[bname], 1), derived))
        records.append(rec)
    for name, kind, cfg, fuse in flt(TRAIN_STEP_CASES
                                     if train_cases is None
                                     else train_cases):
        rec = {"layer": name, "kind": kind, "config": cfg,
               "interpret_mode": interpret_mode(),
               "epilogue": "fused" if fuse else "none",
               # per-layer geometries resolve through the planner's race
               "strategy": "auto",
               "train_step_us": {}}
        fns_s = _train_step_fns(kind, cfg, backends, rng,
                                fuse_epilogue=fuse)
        t_s = _time_interleaved(fns_s, iters=iters, warmup=warmup)
        for bname in backends:
            rec["train_step_us"][bname] = round(t_s[bname], 1)
            derived = "" if bname == "xla_zero_free" else (
                f"vs_xla={t_s['xla_zero_free'] / t_s[bname]:.2f}x")
            rows.append((f"wallclock.train_step.{bname}.{name}",
                         round(t_s[bname], 1), derived))
        records.append(rec)
    # Multi-device train-step rows: one subprocess per (case, device
    # count) so each row gets its own forced host device count; the
    # `train_step_us` field name is shared with the single-device rows,
    # so the delta gate's pallas/xla_zero_free ratio check applies to
    # every device count automatically.
    for name, kind, cfg, fuse, dev_counts in flt(MULTIDEV_TRAIN_CASES
                                                 if multidev_cases is None
                                                 else multidev_cases):
        for n_dev in dev_counts:
            rec = {"layer": f"{name}-d{n_dev}", "kind": kind,
                   "config": cfg, "n_devices": n_dev,
                   "mesh": list(MULTIDEV_MESHES[n_dev]),
                   "interpret_mode": interpret_mode(),
                   "epilogue": "fused" if fuse else "none",
                   "strategy": "auto",
                   "train_step_us": {}}
            t_s = _multidev_time(kind, cfg, fuse, n_dev, iters, warmup,
                                 backends=backends)
            for bname in backends:
                rec["train_step_us"][bname] = round(t_s[bname], 1)
                derived = "" if bname == "xla_zero_free" else (
                    f"vs_xla={t_s['xla_zero_free'] / t_s[bname]:.2f}x")
                rows.append(
                    (f"wallclock.train_step_mdev.{bname}.{name}-d{n_dev}",
                     round(t_s[bname], 1), derived))
            records.append(rec)
    # Serving rows: the ConvServeEngine end to end (admission -> bucket
    # -> jitted launch -> host result), one single-rung-ladder engine per
    # backend arm so the arm isolates the backend, sweeps interleaved
    # like every other family; plus the fault-mode arm (full ladder, 5%
    # seeded kernel faults on the fast rungs) gated on bounded
    # degradation.
    for name, kind, cfg in flt(SERVE_CASES if serve_cases is None
                               else serve_cases):
        from repro.serve.conv_engine import ConvRequest
        from repro.serve.faults import FaultInjector, FaultSchedule
        s_iters = min(iters, _SERVE_MAX_ITERS)
        rec = {"layer": name, "kind": kind, "config": cfg,
               "interpret_mode": interpret_mode(),
               "epilogue": "fused", "strategy": "auto",
               "serve_us": {}, "serve_p99_us": {}, "serve_rps": {},
               "fault": {}}
        payloads = None
        engines = {}
        for bname in backends:
            eng, pshape = _serve_engine(kind, cfg, (bname,))
            engines[bname] = eng
            if payloads is None:
                payloads = [np.asarray(rng.normal(size=pshape), np.float32)
                            for _ in range(cfg["requests"])]
        inj = FaultInjector(FaultSchedule.seeded(
            0, sites=[f"{kind}:pallas", f"{kind}:xla_zero_free"],
            rate=_SERVE_FAULT_RATE, horizon=4096,
            kinds=("kernel_exception",)))
        eng_f, _ = _serve_engine(
            kind, cfg, ("pallas", "xla_zero_free", "reference"),
            injector=inj)
        engines["fault"] = eng_f
        walls = {k: 0.0 for k in engines}
        for _ in range(s_iters):
            for bname, eng in engines.items():
                reqs = [ConvRequest(None, kind, p) for p in payloads]
                t0 = time.perf_counter()
                res = eng.serve(reqs)
                walls[bname] += time.perf_counter() - t0
                if len(res) != len(reqs):
                    raise RuntimeError(
                        f"{name}/{bname}: {len(reqs) - len(res)} of "
                        f"{len(reqs)} requests lost")
        for bname in backends:
            h = engines[bname].health()
            rec["serve_us"][bname] = round(h["p50_us"], 1)
            rec["serve_p99_us"][bname] = round(h["p99_us"], 1)
            rec["serve_rps"][bname] = round(
                s_iters * cfg["requests"] / walls[bname], 1)
            rows.append((f"wallclock.serve.{bname}.{name}",
                         rec["serve_us"][bname],
                         f"p99={rec['serve_p99_us'][bname]}"
                         f";rps={rec['serve_rps'][bname]}"))
        # Bounded-degradation gate: every admitted request completed
        # (checked per sweep above) and every fallback is accounted to an
        # injected fault -- the ladder degrades, it never leaks work.
        h = eng_f.health()
        if h["fallbacks"] > h["kernel_faults"]:
            raise RuntimeError(
                f"{name}/fault: {h['fallbacks']} fallbacks but only "
                f"{h['kernel_faults']} injected faults -- degradation "
                f"is not bounded by the schedule")
        rec["fault"] = {
            "rate": _SERVE_FAULT_RATE,
            "p50_us": round(h["p50_us"], 1),
            "p99_us": round(h["p99_us"], 1),
            "rps": round(s_iters * cfg["requests"] / walls["fault"], 1),
            "completed": h["completed"],
            "kernel_faults": h["kernel_faults"],
            "fallbacks": h["fallbacks"],
            "quarantines": h["quarantines"],
        }
        rows.append((f"wallclock.serve.fault.{name}",
                     rec["fault"]["p50_us"],
                     f"faults={h['kernel_faults']}"
                     f";fallbacks={h['fallbacks']}"
                     f";completed={h['completed']}"))
        records.append(rec)
    # Elastic-training rows (DESIGN.md Sec. 2.12): the guarded vs
    # unguarded ConvTrainer step interleaved on pallas (the gated
    # overhead ratio), plus ONE seeded supervisor recovery drill in a
    # forced-device subprocess (accounting, not a timing sweep).
    for name, kind, cfg in flt(ELASTIC_TRAIN_CASES if elastic_cases
                               is None else elastic_cases):
        rec = {"layer": name, "kind": kind, "config": cfg,
               "interpret_mode": interpret_mode(),
               "epilogue": "fused", "strategy": "auto",
               "train_step_guard_us": {}, "recovery": {}}
        t_g = _time_interleaved(_guard_step_fns(kind, cfg),
                                iters=min(iters, _ELASTIC_MAX_ITERS),
                                warmup=warmup)
        for label in ("pallas", "pallas_unguarded"):
            rec["train_step_guard_us"][label] = round(t_g[label], 1)
        rows.append((f"wallclock.elastic_train.guard.{name}",
                     rec["train_step_guard_us"]["pallas"],
                     f"guard_overhead="
                     f"{t_g['pallas'] / t_g['pallas_unguarded']:.2f}x"))
        rec["recovery"] = _elastic_recovery(kind, cfg)
        rows.append((f"wallclock.elastic_train.recovery.{name}",
                     rec["recovery"]["recovery_wallclock_s"],
                     f"steps_lost={rec['recovery']['steps_lost']}"
                     f";recompiles={rec['recovery']['recompiles']}"
                     f";completed={rec['recovery']['completed_steps']}"))
        records.append(rec)
    if records_out is not None:
        records_out.extend(records)
    if write_json:
        path = BENCH_JSON if json_path is None else pathlib.Path(json_path)
        path.write_text(json.dumps(
            {"note": "conv backend wall-clock (us/call): median-of-iters, "
                     "backends interleaved per case (PR 4 methodology -- "
                     "NOT comparable to the pre-PR-4 min-of-iters rows); "
                     "pallas runs in interpret mode off-TPU, so absolute "
                     "numbers are only comparable within a backend+host "
                     "class; `tiling` records the planner decision each "
                     "pallas row ran under; `backward_us.pallas` is the "
                     "FUSED dual-gradient launch vs the `two_launch` "
                     "pallas pair it replaced; `train_step_us` rows time "
                     "one full jit'd SGD step (fwd + fused bwd + update); "
                     "`epilogue` tags each row's fused tail ('none' for "
                     "the plain families), and the *_ep_us families "
                     "carry a `pallas_unfused` arm -- the same pallas "
                     "kernels with the tail/mask/db as separate XLA ops; "
                     "`mdev-*` rows re-time the train step on a forced "
                     "host-platform device mesh (`n_devices`/`mesh`) "
                     "through the shard_map conv dispatch layer, one "
                     "subprocess per device count; `strategy` is the "
                     "strategy planner's per-geometry pick (phase vs "
                     "predicated implicit-GEMM; 'auto' on train rows "
                     "where it resolves per layer) and `winner` the "
                     "measured head-to-head of the two forced-strategy "
                     "pallas_* arms on the input-grad families; "
                     "`serve-*` rows time the geometry-bucketed "
                     "ConvServeEngine end to end (admission -> slot "
                     "batch -> jitted launch -> host result), one "
                     "single-rung ladder per backend arm "
                     "(`serve_us`=p50, plus p99 and requests/s), and "
                     "`fault` re-times the full degradation ladder "
                     "under a seeded 5% kernel-fault schedule, gated "
                     "on bounded degradation; `elastic-train-*` rows "
                     "time the ConvTrainer's GUARDED jitted step (in-"
                     "graph all-finite flag, same launch count) against "
                     "the `pallas_unguarded` step -- the gated guard-"
                     "overhead ratio -- and `recovery` records one "
                     "seeded RunSupervisor drill (host loss + NaN "
                     "steps at the row's fault_seed, forced-device "
                     "subprocess): steps lost, recompiles, recovery "
                     "wallclock",
             "cases": records}, indent=2) + "\n")
        rows.append(("wallclock.conv_backend.json", str(path), ""))
    return rows


# ---------------------------------------------------------------------------
# CI delta gate: re-time the committed geometries, fail on pallas
# regression vs BENCH_conv.json
# ---------------------------------------------------------------------------

# Per-op timing fields and the baseline each op's pallas number is
# normalized against.  Ratios -- pallas / same-row baseline -- are the
# host-class-portable quantity (the JSON's own note: absolute us are only
# comparable within a backend+host class, and CI does not run on the
# host that generated the committed file).  The fused backward gates
# against the SAME-row two-launch pallas pair (a fused/two-launch ratio
# regression > 1.5x means the fusion itself lost its reason to exist);
# the train-step rows gate against the xla_zero_free step like the
# per-op families.
_GATE_FIELDS = {
    "tconv_us": "xla_zero_free",
    "filter_grad_us": "xla_zero_free",
    "dilated_forward_us": "xla_zero_free",
    "input_grad_us": "xla_zero_free",
    "backward_us": "two_launch",
    "train_step_us": "xla_zero_free",
    # Epilogue families: forwards gate against the XLA zero-free tail
    # composition; backwards gate against the SAME pallas kernels with
    # the tail unfused -- a fused/unfused ratio regression > threshold
    # means the epilogue fusion itself stopped paying for its launch.
    "forward_ep_us": "xla_zero_free",
    "backward_ep_us": "pallas_unfused",
    "tconv_ep_us": "xla_zero_free",
    "ct_backward_ep_us": "pallas_unfused",
    # Serving p50: the pallas arm gates against the xla_zero_free arm of
    # the same row -- a ratio regression means the fused kernels lost
    # ground inside the identical engine path.
    "serve_us": "xla_zero_free",
    # Elastic training: the guarded step gates against the SAME step
    # unguarded -- the numerics guard is contractually a few fused XLA
    # reductions (same launch count), so a ratio regression means the
    # guard grew a real cost.
    "train_step_guard_us": "pallas_unguarded",
}


def delta_gate(threshold=1.5, iters=21, warmup=2):
    """Re-run every committed BENCH_conv.json geometry on this host and
    fail (RuntimeError) if any pallas timing regresses more than
    `threshold`x against its committed row.

    `iters` defaults higher than the plain bench: the gate's job is a
    stable ratio, and on noisy shared hosts the interleaved median needs
    ~20 sweeps before its run-to-run spread sits well inside the 1.5x
    threshold.

    Comparison is by pallas/baseline RATIO, and only between rows of the
    same host class (`interpret_mode` must match): a ratio regression
    means the fused kernel lost ground against the dense zero-free
    baseline *on the same host in the same run*, which is the signal a
    kernel/tiling change actually degraded -- absolute us would just
    flag every hardware difference between CI and the committing host.
    """
    committed = {rec["layer"]: rec
                 for rec in json.loads(BENCH_JSON.read_text())["cases"]}
    records = []
    rows = conv_backend_bench(iters=iters, warmup=warmup,
                              write_json=False, records_out=records)
    failures, compared, skipped = [], 0, 0
    # `strategy` (planner pick) and `winner` (measured race) are
    # host/timing-dependent, not geometry -- like `tiling`, they must
    # not trip the drift check when a model retune flips them.
    # `recovery` is wallclock/host-dependent accounting, like `fault`.
    timing_keys = set(_GATE_FIELDS) | {"tiling", "interpret_mode",
                                       "strategy", "winner",
                                       "serve_p99_us", "serve_rps",
                                       "fault", "recovery"}
    for rec in records:
        base = committed.get(rec["layer"])
        if base is None or base.get("interpret_mode") != \
                rec.get("interpret_mode"):
            skipped += 1
            continue
        # A name can only gate against the SAME conv: if the case's
        # geometry fields drifted from the committed row (edited without
        # regenerating the JSON), comparing ratios of different problems
        # would be silently meaningless -- fail loudly instead.
        geom_drift = [k for k in sorted(set(rec) & set(base) - timing_keys)
                      if rec[k] != base[k]]
        if geom_drift:
            failures.append(
                f"{rec['layer']}: geometry drift vs committed row on "
                f"{geom_drift} -- regenerate BENCH_conv.json")
            continue
        for field, baseline in _GATE_FIELDS.items():
            if field not in rec or field not in base:
                continue
            new_p, new_b = rec[field].get("pallas"), \
                rec[field].get(baseline)
            old_p, old_b = base[field].get("pallas"), \
                base[field].get(baseline)
            if None in (new_p, new_b, old_p, old_b) or not old_p \
                    or not new_b or not old_b:
                continue
            compared += 1
            new_ratio, old_ratio = new_p / new_b, old_p / old_b
            if new_ratio > threshold * old_ratio:
                failures.append(
                    f"{rec['layer']}.{field}: pallas/{baseline} ratio "
                    f"{new_ratio:.2f} vs committed {old_ratio:.2f} "
                    f"(> {threshold}x)")
    if failures:
        raise RuntimeError(
            "pallas perf regression vs BENCH_conv.json:\n  "
            + "\n  ".join(failures))
    if compared == 0:
        raise RuntimeError(
            "delta gate compared ZERO ratios (all rows skipped: "
            f"skipped={skipped}) -- a vacuous pass would hide every "
            "regression; check host class / BENCH_conv.json layer names")
    rows.append(("wallclock.delta_gate", "ok",
                 f"{compared} ratios within {threshold}x"
                 f";skipped={skipped}"))
    return rows


# ---------------------------------------------------------------------------
# CI smoke: one tiny geometry per op family + BENCH_conv.json schema guard
# ---------------------------------------------------------------------------

# Smoke geometries: minimal sizes that still exercise every op family
# (tconv, filter-grad, fused dual-gradient backward, dilated forward,
# strided+dilated input grad, epilogue-fused forward/backward for both
# direct and transposed conv, CNN/GAN train step -- the GAN one with the
# fused epilogue path on) through both zero-free backends in seconds on
# an interpret-mode host.
SMOKE_CASES = [("smoke-tconv", 5, 3, 2, 4, 4)]
SMOKE_DILATED_CASES = [("smoke-d2", 9, 3, 1, 2, 2, 4, 4)]
SMOKE_STRIDED_DILATED_CASES = [("smoke-s2d2", 4, 3, 2, 1, 2, 4, 4)]
SMOKE_TRAIN_CASES = [
    ("smoke-train-cnn", "cnn",
     {"widths": [4], "batch": 1, "image": 8, "n_classes": 4}, False),
    ("smoke-train-gan-gen-ep", "gan_gen",
     {"base": 4, "z_dim": 8, "batch": 1}, True),
]
# One 2-device row: exercises the subprocess launcher, the shard_map
# dispatch layer, and the sharded param/batch placement end to end.
SMOKE_MULTIDEV_CASES = [
    ("smoke-mdev-train-cnn-ep", "cnn",
     {"widths": [4], "batch": 4, "image": 8, "n_classes": 4}, True, [2]),
]
SMOKE_EPILOGUE_CASES = [
    ("smoke-ep-brelu", 4, 3, 2, 4, 4,
     Epilogue(activation="relu", bias=True)),
]
SMOKE_TCONV_EPILOGUE_CASES = [
    ("smoke-tconv-ep-tanh", 4, 3, 2, 4, 4,
     Epilogue(activation="tanh")),
]
# One tiny serve row: exercises admission, bucketing, the per-arm
# single-rung ladders, AND the fault-mode full-ladder arm end to end.
SMOKE_SERVE_CASES = [
    ("smoke-serve-gan-gen", "gan_gen",
     {"z_dim": 8, "base": 4, "out_ch": 3, "slot_batch": 1,
      "requests": 2}),
]
# One tiny elastic-training row: guarded-vs-unguarded step plus a
# 2-device / 2-host supervisor recovery drill in a subprocess.
SMOKE_ELASTIC_CASES = [
    ("smoke-elastic-train-cnn", "cnn",
     {"widths": [4], "batch": 4, "image": 8, "n_classes": 4,
      "total_steps": 4, "ckpt_every": 2, "backend": "xla_zero_free",
      "n_devices": 2, "hosts": 2, "fault_seed": 4,
      "host_rate": 0.12, "nan_rate": 0.2}),
]


def _record_schema(doc) -> set[frozenset]:
    """The set of per-record key signatures -- one frozenset per op
    family (tconv/filter-grad, dilated-forward, strided+dilated)."""
    return {frozenset(rec) for rec in doc["cases"]}


def smoke():
    """Run one tiny geometry per op family end to end and fail on
    BENCH_conv.json schema drift.

    The timed paths are the real backend entry points, so a wiring break
    in any op family fails here in CI instead of at the next perf
    comparison; the generated record schema is diffed against the
    committed BENCH_conv.json so a field rename/removal (or a new op
    family whose rows were never regenerated) is caught the same way.
    The smoke JSON is written next to BENCH_conv.json and removed after
    the check -- the committed trajectory file is never clobbered.
    """
    smoke_json = BENCH_JSON.with_name(BENCH_JSON.stem + ".smoke.json")
    try:
        rows = conv_backend_bench(
            iters=1, warmup=1, cases=SMOKE_CASES,
            dilated_cases=SMOKE_DILATED_CASES,
            strided_dilated_cases=SMOKE_STRIDED_DILATED_CASES,
            train_cases=SMOKE_TRAIN_CASES,
            epilogue_cases=SMOKE_EPILOGUE_CASES,
            tconv_epilogue_cases=SMOKE_TCONV_EPILOGUE_CASES,
            multidev_cases=SMOKE_MULTIDEV_CASES,
            serve_cases=SMOKE_SERVE_CASES,
            elastic_cases=SMOKE_ELASTIC_CASES,
            json_path=smoke_json)
        got = _record_schema(json.loads(smoke_json.read_text()))
        committed_doc = json.loads(BENCH_JSON.read_text())
        want = _record_schema(committed_doc)
        if got != want:
            only_new = [sorted(s) for s in got - want]
            only_old = [sorted(s) for s in want - got]
            raise RuntimeError(
                "BENCH_conv.json schema drift: regenerate it with "
                "`python -m benchmarks.run` (record signatures only in "
                f"smoke run: {only_new}; only in committed file: "
                f"{only_old})")
        if set(committed_doc) != {"note", "cases"}:
            raise RuntimeError(
                f"BENCH_conv.json top-level drift: {sorted(committed_doc)}")
    finally:
        smoke_json.unlink(missing_ok=True)
    rows.append(("wallclock.smoke.schema", "ok",
                 f"{len(SMOKE_CASES + SMOKE_DILATED_CASES + SMOKE_STRIDED_DILATED_CASES + SMOKE_TRAIN_CASES + SMOKE_MULTIDEV_CASES + SMOKE_EPILOGUE_CASES + SMOKE_TCONV_EPILOGUE_CASES + SMOKE_SERVE_CASES + SMOKE_ELASTIC_CASES)}"
                 " families"))
    return rows


if __name__ == "__main__":
    for r in run() + conv_backend_bench():
        print(",".join(str(c) for c in r))
