"""Shared test fixtures/helpers.

NOTE: tests must see the default single CPU device -- do NOT set
XLA_FLAGS=--xla_force_host_platform_device_count here (the dry-run sets it
in its own process).  Tests that need a multi-device mesh spawn a
subprocess (see tests/test_multidevice.py).
"""
from __future__ import annotations

import numpy as np
import pytest

try:  # optional dev dependency (see requirements-dev.txt)
    import hypothesis  # noqa: F401
except ImportError:  # graceful fallback: deterministic property-test shim
    from _hypothesis_shim import install as _install_hypothesis_shim
    _install_hypothesis_shim()


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def assert_allclose(a, b, rtol=1e-4, atol=1e-4, err_msg=""):
    np.testing.assert_allclose(np.asarray(a, np.float64),
                               np.asarray(b, np.float64),
                               rtol=rtol, atol=atol, err_msg=err_msg)


# ---------------------------------------------------------------------------
# jaxpr inspection helpers (shared by the structural-guarantee tests in
# test_dispatch.py and test_dilated_parity.py -- one traversal, so a fix
# for a new higher-order primitive reaches every suite)
# ---------------------------------------------------------------------------

def walk_eqns(jaxpr):
    """Yield every eqn in a (closed) jaxpr, recursing into sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)  # ClosedJaxpr
            if sub is not None:
                yield from walk_eqns(sub)
            elif hasattr(v, "eqns"):         # raw Jaxpr
                yield from walk_eqns(v)


def walk_eqns_outside_pallas(jaxpr):
    """Like `walk_eqns`, but does NOT descend into pallas_call kernel
    bodies: the epilogue-fusion pins assert that bias/activation/mask
    eqns exist ONLY inside the kernels, so the in-kernel eqns must not
    leak into the 'outside' traversal."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call":
            continue
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", None)
            if sub is not None:
                yield from walk_eqns_outside_pallas(sub)
            elif hasattr(v, "eqns"):
                yield from walk_eqns_outside_pallas(v)


def count_pallas_calls(fn, *args) -> int:
    import jax
    jaxpr = jax.make_jaxpr(fn)(*args)
    return sum(1 for e in walk_eqns(jaxpr.jaxpr)
               if e.primitive.name == "pallas_call")


def pallas_grids(fn, *args):
    """Grid tuples of every pallas_call in the traced jaxpr."""
    import jax
    jaxpr = jax.make_jaxpr(fn)(*args)
    return [tuple(e.params["grid_mapping"].grid)
            for e in walk_eqns(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"]


def pallas_block_shapes(fn, *args):
    """Per pallas_call in the traced jaxpr: the list of block shapes of
    every in/out BlockSpec (the kernel's VMEM working set)."""
    import jax
    jaxpr = jax.make_jaxpr(fn)(*args)
    return [[tuple(getattr(d, "block_size", d) for d in bm.block_shape)
             for bm in e.params["grid_mapping"].block_mappings]
            for e in walk_eqns(jaxpr.jaxpr)
            if e.primitive.name == "pallas_call"]


def max_intermediate_size(fn, *args) -> int:
    """Largest array (elements) produced by any eqn in the traced jaxpr."""
    import jax
    jaxpr = jax.make_jaxpr(fn)(*args)
    sizes = [int(np.prod(v.aval.shape))
             for e in walk_eqns(jaxpr.jaxpr) for v in e.outvars
             if hasattr(v.aval, "shape")]
    return max(sizes)
