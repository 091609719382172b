"""Pipeline parallelism: GPipe-schedule microbatch pipeline built from
shard_map + lax.ppermute over a "stage" mesh axis.

The production meshes for this paper's workloads are (data, model) --
EcoFlow's own technique has no pipeline dimension -- but at >=1000-node
scale a stage axis is how the 94-layer MoE would hide inter-pod latency,
so the substrate ships one, tested on CPU with a small stage count.

Usage:
    stages = [stage_fn] * n_stages       # same fn, stage-sliced params
    y = gpipe(mesh, "stage", stage_fn, params_stacked, x, n_microbatches)

`params_stacked` leaves have a leading stage dim, sharded over the stage
axis; `x` is (n_micro * micro_batch, ...) sharded over the stage axis on
dim 0 only virtually (each stage works on a rotating microbatch window).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P


def gpipe(mesh: Mesh, axis: str, stage_fn: Callable, stage_params, x,
          n_micro: int):
    """Run a GPipe pipeline of size mesh.shape[axis].

    stage_fn(params_slice, x_micro) -> x_micro; applied in sequence over
    stages with microbatches flowing via ppermute.  x: (n_micro, mb, ...).
    Returns y with the same shape.
    """
    n_stages = mesh.shape[axis]
    assert x.shape[0] == n_micro

    def per_stage(params, xs):
        # params: this stage's slice (leading dim 1); xs: all microbatches
        # (n_micro, mb, ...) -- only stage 0's copy is "real" input.
        params = jax.tree.map(lambda a: a[0], params)
        stage = lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(xs[0])

        def tick(carry, t):
            buf, ys = carry
            # Stage 0 injects microbatch t (if any); others use the buffer
            # handed over from the previous stage on the previous tick.
            inject = xs[jnp.minimum(t, n_micro - 1)]
            cur = jnp.where(stage == 0, inject, buf)
            out = stage_fn(params, cur)
            # Hand off to the next stage.
            nxt = lax.ppermute(out, axis,
                               [(i, (i + 1) % n_stages)
                                for i in range(n_stages)])
            # The last stage emits microbatch (t - (n_stages-1)) at tick t.
            emit_idx = t - (n_stages - 1)
            ys = jnp.where(
                (stage == n_stages - 1) & (emit_idx >= 0) &
                (emit_idx < n_micro),
                ys.at[jnp.clip(emit_idx, 0, n_micro - 1)].set(out), ys)
            return (nxt, ys), None

        ys0 = jnp.zeros_like(xs)
        # carries become stage-varying after the first ppermute; mark the
        # initial values as varying over the stage axis.
        buf = lax.pcast(buf, (axis,), to="varying")
        ys0 = lax.pcast(ys0, (axis,), to="varying")
        (_, ys), _ = lax.scan(tick, (buf, ys0), jnp.arange(n_ticks))
        # Broadcast the last stage's outputs to everyone.
        ys = lax.psum(jnp.where(stage == n_stages - 1, ys, 0.0), axis)
        return ys

    pspec_params = jax.tree.map(lambda a: P(axis, *([None] * (a.ndim - 1))),
                                stage_params)
    f = jax.shard_map(per_stage, mesh=mesh,
                      in_specs=(pspec_params, P(*([None] * x.ndim))),
                      out_specs=P(*([None] * x.ndim)))
    return f(stage_params, x)
