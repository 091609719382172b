"""Shared tap-window machinery for the Pallas conv kernels.

Every conv kernel family realizes the same EcoFlow primitive -- the
per-tap multicast group: a window of a VMEM-resident block at the tap's
offset, subsampled by the output stride.  The host-side window-fit guard
and the in-kernel window read both live here so a fix to the window math
reaches every kernel (the B>1 re-fetch lesson: one-sided fixes to
duplicated scaffolding go stale silently).

Windows are read straight from the kernel's block REF with `pl.ds`
(stride and all), never sliced out of a loaded value: Mosaic lowers a
strided ref read to one strided VMEM load, but a strided or dynamic slice
of a value to a gather (or not at all), which it refuses.  The same read
serves static offsets (unrolled taps) and traced ones (taps on the grid).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def tap_window_extent(o: int, s: int, d: int, k: int) -> int:
    """Padded-input extent needed so the tap window fits for every tap:
    (O-1)*S + D*(K-1) + 1 per axis."""
    return (o - 1) * s + d * (k - 1) + 1


def pad_to_tap_windows(xp: jax.Array, *, stride, dilation, k,
                       out_size) -> jax.Array:
    """Tail-pad an NHWC padded input so every (kx*D, ky*D) tap window
    fits.  The out_size floor already guarantees the fit for exact and
    non-exact geometries; this guard makes the kernels robust to any
    caller-supplied padding."""
    sh, sw = stride
    dh, dw = dilation
    kh, kw = k
    oh, ow = out_size
    need_h = tap_window_extent(oh, sh, dh, kh)
    need_w = tap_window_extent(ow, sw, dw, kw)
    if xp.shape[1] < need_h or xp.shape[2] < need_w:
        xp = jnp.pad(xp, ((0, 0), (0, max(0, need_h - xp.shape[1])),
                          (0, max(0, need_w - xp.shape[2])), (0, 0)))
    return xp


def split_index(step, per: int, j: int, minor: int):
    """(i // minor, i % minor) of the flat index i = step*per + j of an
    unrolled grid axis (`per` entries per grid step, `step` the grid
    index or 0).  The minor part is a python int whenever `per` is a
    multiple of `minor`: the W offset of a window, a sublane index that
    Mosaic must see statically, then stays static even when the taps are
    on the grid (the compiled planner only emits such unrolls)."""
    if isinstance(step, int) or per % minor:
        i = step * per + j
        return i // minor, i % minor
    return step * (per // minor) + j // minor, j % minor


def read_window(ref, lead: tuple, h0, w0, *, oh: int, ow: int, sh: int = 1,
                sw: int = 1) -> jax.Array:
    """ref[*lead, h0 + i*sh, w0 + j*sw, :] for i < oh, j < ow: one
    (strided) read of a (.., H, W, C) block ref.  `h0` / `w0` may be
    python ints or traced scalars."""
    return ref[(*lead, pl.ds(h0, oh, stride=sh), pl.ds(w0, ow, stride=sw),
                slice(None))]


def gather_tap(ref, lead: tuple, kx, ky, *, sh: int, sw: int, dh: int,
               dw: int, oh: int, ow: int) -> jax.Array:
    """In-kernel per-tap multicast group: the (oh, ow, C) window of the
    resident (.., H, W, C) block at tap offset (kx*D, ky*D), subsampled
    by the stride -- x[i*S + kx*D, j*S + ky*D, :].  (kx, ky) may be
    traced (derived from a grid index) or python ints (unrolled taps)."""
    return read_window(ref, lead, kx * dh, ky * dw, oh=oh, ow=ow, sh=sh,
                       sw=sw)
