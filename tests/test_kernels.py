"""Per-kernel shape/dtype sweeps: every Pallas kernel (interpret mode on
CPU) against its pure-jnp oracle in kernels/ref.py."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import ecoflow
from repro.kernels import ops, ref
from repro.kernels.attention import flash_attention_pallas
from repro.kernels.dconv_filtergrad import dconv_filter_grad_pallas
from repro.kernels.dconv_forward import dconv_forward_pallas
from repro.kernels.tconv_phase import pack_phase_filters, tconv_fused_pallas

from conftest import (assert_allclose, pallas_block_shapes,
                      pallas_grids as _pallas_grids)


# ---------------------------------------------------------------------------
# tconv_phase (phase-decomposed transposed conv)
# ---------------------------------------------------------------------------

TCONV_SWEEP = [
    # (B, O, K, S, P, Ci, Co)
    (1, 4, 3, 2, 0, 4, 4),
    (2, 5, 3, 2, 1, 3, 5),
    (2, 7, 4, 3, 0, 8, 2),
    (1, 3, 11, 4, 2, 2, 3),
    (1, 6, 2, 4, 0, 5, 5),       # K < S: empty phases exist
    (2, 4, 1, 1, 0, 4, 4),       # pointwise stride 1
    (1, 8, 5, 2, 2, 130, 7),     # Cin > default tile
    (1, 4, 3, 2, 0, 3, 130),     # Cout > default tile (dy block tiled)
]


@pytest.mark.parametrize("B,O,K,S,P,Ci,Co", TCONV_SWEEP)
def test_tconv_phase_sweep(rng, B, O, K, S, P, Ci, Co):
    dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
    N = S * (O - 1) + K - 2 * P
    out = ops.tconv_phase(dy, w, stride=(S, S), padding=(P, P),
                          n_out=(N, N))
    want = ref.tconv_phase_ref(dy, w, stride=(S, S), padding=(P, P),
                               n_out=(N, N))
    assert_allclose(out, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 5e-2)])
def test_tconv_phase_dtypes(rng, dtype, tol):
    B, O, K, S, Ci, Co = 2, 5, 3, 2, 4, 6
    dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), dtype)
    w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), dtype)
    N = S * (O - 1) + K
    out = ops.tconv_phase(dy, w, stride=(S, S), padding=(0, 0),
                          n_out=(N, N))
    assert out.dtype == dtype
    want = ref.tconv_phase_ref(dy, w, stride=(S, S), padding=(0, 0),
                               n_out=(N, N))
    assert_allclose(out, want, rtol=tol, atol=tol)


def test_tconv_fused_direct_call(rng):
    """The fused kernel entry point itself (not via ops) matches the
    oracle, including the default exact-fit n_out."""
    B, O, K, S, Ci, Co = 2, 6, 5, 2, 5, 4
    dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
    out = tconv_fused_pallas(dy, w, stride=(S, S), interpret=True)
    N = S * (O - 1) + K
    want = ref.tconv_phase_ref(dy, w, stride=(S, S), padding=(0, 0),
                               n_out=(N, N))
    assert_allclose(out, want, rtol=1e-4, atol=1e-4)


TCONV_DILATED_SWEEP = [
    # (B, O, K, S, P, D, Ci, Co): input gradient of a forward conv with
    # stride S AND filter dilation D -- the unified (phase, tap) kernel.
    (1, 5, 3, 2, 1, 2, 3, 4),    # gcd(S,D)=2: half the residues empty
    (2, 4, 3, 2, 0, 3, 2, 3),    # coprime S, D
    (1, 4, 3, 3, 2, 2, 3, 2),
    (2, 5, 2, 3, 0, 3, 2, 2),    # S == D: one tap-phase per axis
    (2, 6, 3, 1, 2, 2, 3, 3),    # stride-1 atrous adjoint
    (1, 3, 5, 6, 1, 4, 2, 2),    # period 3, ragged phases
]


@pytest.mark.parametrize("B,O,K,S,P,D,Ci,Co", TCONV_DILATED_SWEEP)
def test_tconv_phase_dilated_sweep(rng, B, O, K, S, P, D, Ci, Co):
    dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
    N = S * (O - 1) + D * (K - 1) + 1 - 2 * P
    out = ops.tconv_phase(dy, w, stride=(S, S), padding=(P, P),
                          n_out=(N, N), dilation=(D, D))
    want = ref.tconv_phase_ref(dy, w, stride=(S, S), padding=(P, P),
                               n_out=(N, N), dilation=(D, D))
    assert_allclose(out, want, rtol=1e-4, atol=1e-4)


def test_tconv_cout_tiled_dy_block(rng):
    """The dy block carries a Cout TILE, not full channel depth: with
    Cout > cout_tile the grid gains a sequential Cout axis and the
    in-kernel dy/weight blocks are capped at the tile -- and the result
    still matches the oracle (accumulation across Cout tiles)."""
    B, O, K, S, P, Ci, Co, tile = 1, 4, 3, 2, 0, 5, 20, 8
    dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
    N = S * (O - 1) + K
    fn = lambda dy_, w_: tconv_fused_pallas(
        dy_, w_, stride=(S, S), padding=(P, P), n_out=(N, N),
        cout_tile=tile, cin_tile=4, tap_unroll=1, interpret=True)
    grids = _pallas_grids(fn, dy, w)
    assert len(grids) == 1
    # grid (B, T, Cin_t, Cout_t, TK): sequential Cout axis of ceil(Co/tile).
    assert grids[0][3] == -(-Co // tile), grids[0]
    blocks = pallas_block_shapes(fn, dy, w)[0]
    dy_block, w_block, out_block = blocks
    assert dy_block[-1] == tile, blocks        # dy: Cout tile, not Co
    assert w_block[-2:] == (tile, 4), blocks   # w: (Co_t, Ci_t)
    assert out_block[-1] == 4, blocks          # out: Cin tile
    out = fn(dy, w)
    want = ref.tconv_phase_ref(dy, w, stride=(S, S), padding=(P, P),
                               n_out=(N, N))
    assert_allclose(out, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("S", [2, 3, 4])
@pytest.mark.parametrize("K", [3, 4, 5])
def test_pack_phase_filters_single_source_of_truth(rng, S, K):
    """`pack_phase_filters` consumes `ecoflow.phase_subfilters` (the one
    rotation convention shared with the dense XLA backend) and only adds
    uniform-shape packing.  This pins the padding/rotation commutation the
    refactor relies on: FRONT-padding the flipped sub-filter equals
    TAIL-padding before the flip (the old inline convention)."""
    Ci, Co = 3, 4
    w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
    packed = pack_phase_filters(w, (S, S))
    KP = -(-K // S)
    # Old convention, inlined: tail-pad the raw sub-filter, then rotate.
    expect = []
    for p in range(min(S, K)):
        for q in range(min(S, K)):
            sub = w[p::S, q::S]
            kp, kq = sub.shape[0], sub.shape[1]
            sub = jnp.pad(sub, ((0, KP - kp), (0, KP - kq), (0, 0), (0, 0)))
            sub = jnp.flip(sub, axis=(0, 1))
            expect.append(jnp.swapaxes(sub, 2, 3))
    expect = jnp.stack(expect)
    assert packed.shape == expect.shape
    assert_allclose(packed, expect, rtol=0, atol=0)
    # And the packed taps are exactly the phase_subfilters' taps.
    subs = ecoflow.phase_subfilters(w, (S, S))
    for p in range(min(S, K)):
        for q in range(min(S, K)):
            sub = subs[p][q]
            kp, kq = sub.shape[0], sub.shape[1]
            got = packed[p * min(S, K) + q, KP - kp:, KP - kq:]
            assert_allclose(got, sub, rtol=0, atol=0)


def test_pack_phase_filters_zero_free(rng):
    """Packing is tap-exhaustive and zero-free: every filter tap lands in
    exactly one phase slot, ragged phases are zero-padded."""
    K, S, Ci, Co = 5, 2, 3, 4
    w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
    packed = pack_phase_filters(w, (S, S))      # (S*S, KP, KQ, Co, Ci)
    KP = -(-K // S)
    assert packed.shape == (S * S, KP, KP, Co, Ci)
    # sum over all phase slots of |packed| == sum over all taps of |w|
    assert_allclose(jnp.abs(packed).sum(), jnp.abs(w).sum(), rtol=1e-5)
    # stride > K: only the min(S,K)^2 non-empty phases are packed; the
    # structurally-zero phases get no grid steps (wrapper zero-fills them)
    w1 = jnp.asarray(rng.normal(size=(2, 2, 3, 4)), jnp.float32)
    packed1 = pack_phase_filters(w1, (4, 4))
    assert packed1.shape[0] == 4  # (p,q) in {0,1}^2
    assert all(float(jnp.abs(packed1[t]).sum()) > 0 for t in range(4))


# ---------------------------------------------------------------------------
# dconv_filtergrad (zero-free filter gradient)
# ---------------------------------------------------------------------------

DCONV_SWEEP = [
    (1, 9, 3, 2, 0, 4, 4),
    (2, 9, 3, 2, 1, 3, 5),
    (3, 13, 4, 3, 0, 2, 7),
    (1, 23, 11, 4, 2, 2, 3),
    (2, 8, 1, 2, 0, 5, 6),
    (1, 10, 3, 1, 1, 130, 3),    # Cin > default tile, stride 1
]


@pytest.mark.parametrize("B,N,K,S,P,Ci,Co", DCONV_SWEEP)
def test_dconv_filtergrad_sweep(rng, B, N, K, S, P, Ci, Co):
    O = (N + 2 * P - K) // S + 1
    x = jnp.asarray(rng.normal(size=(B, N, N, Ci)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
    dw = ops.dconv_filter_grad(x, dy, stride=(S, S), padding=(P, P),
                               k=(K, K))
    want = ref.dconv_filter_grad_ref(x, dy, stride=(S, S), padding=(P, P),
                                     k=(K, K))
    assert_allclose(dw, want, rtol=1e-4, atol=1e-4)


DCONV_DILATED_SWEEP = [
    # (B, N, K, S, P, D, Ci, Co): forward filter dilation D
    (1, 11, 3, 1, 2, 2, 3, 4),
    (2, 15, 3, 1, 4, 4, 2, 3),
    (1, 14, 3, 2, 1, 2, 3, 2),
    (2, 17, 2, 3, 0, 4, 2, 5),
]


@pytest.mark.parametrize("B,N,K,S,P,D,Ci,Co", DCONV_DILATED_SWEEP)
def test_dconv_filtergrad_dilated_sweep(rng, B, N, K, S, P, D, Ci, Co):
    """Filter gradient of a *dilated* forward conv: tap windows at
    spacing D inside the kernel."""
    k_eff = D * (K - 1) + 1
    O = (N + 2 * P - k_eff) // S + 1
    x = jnp.asarray(rng.normal(size=(B, N, N, Ci)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
    dw = ops.dconv_filter_grad(x, dy, stride=(S, S), padding=(P, P),
                               k=(K, K), dilation=(D, D))
    want = ref.dconv_filter_grad_ref(x, dy, stride=(S, S), padding=(P, P),
                                     k=(K, K), dilation=(D, D))
    assert_allclose(dw, want, rtol=1e-4, atol=1e-4)


def test_filter_grad_spatially_tiled_batch_sequential(rng):
    """Block-shape pins for the rebuilt filter-grad grid: with a spatial
    tile the x block holds ONE overlapping slab -- never the full
    Hp x Wp padded frame -- the out block carries ALL taps of a channel
    tile (stationary across the sequential (B, SP, tap) axes, no
    (B, T, Ci, Co) HBM partials), and the result still matches the
    oracle (fp32 accumulation across batch and spatial slabs)."""
    B, N, K, S, P, Ci, Co = 2, 33, 3, 2, 0, 12, 20
    O = (N - K) // S + 1                     # 16 output rows
    ci_t, co_t, sp, u = 8, 8, 4, 3
    x = jnp.asarray(rng.normal(size=(B, N, N, Ci)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
    fn = lambda x_, dy_: dconv_filter_grad_pallas(
        x_, dy_, stride=(S, S), padding=(P, P), k=(K, K),
        cin_tile=ci_t, cout_tile=co_t, spatial_tile=sp, tap_unroll=u,
        interpret=True)
    grids = _pallas_grids(fn, x, dy)
    assert len(grids) == 1
    n_sp = -(-O // sp)
    # grid (Cin_t, Cout_t, B, SP, T'): batch + spatial SEQUENTIAL.
    assert grids[0] == (-(-Ci // ci_t), -(-Co // co_t), B, n_sp,
                        K * K // u), grids[0]
    x_block, dy_block, out_block = pallas_block_shapes(fn, x, dy)[0]
    rows_x = (sp - 1) * S + (K - 1) + 1      # slab rows incl. tap halo
    hp = (O - 1) * S + K                     # full padded frame rows
    assert x_block[2] == rows_x < hp, (x_block, hp)
    assert x_block[-1] == ci_t, x_block      # channel tile, not Ci
    assert dy_block[2:] == (sp, O, co_t), dy_block
    # out block: ALL K*K taps of one (ci, co) tile -- the accumulator is
    # stationary, so there is no (B, T, Ci, Co) partial to reduce.
    assert out_block == (K * K, ci_t, co_t), out_block
    dw = fn(x, dy)
    want = ref.dconv_filter_grad_ref(x, dy, stride=(S, S), padding=(P, P),
                                     k=(K, K))
    assert_allclose(dw, want, rtol=1e-4, atol=1e-4)


RAGGED_TILE_SWEEP = [
    # (B, N, K, S, P, Ci, Co, ci_t, co_t, sp, u): tiles that do NOT
    # divide the channel counts, plus spatial tiles that do not divide O.
    (2, 9, 3, 2, 0, 13, 21, 8, 16, 3, 9),
    (3, 11, 3, 1, 1, 5, 7, 4, 4, 4, 1),
    (1, 23, 11, 4, 2, 3, 5, 2, 4, 2, 11),
]


@pytest.mark.parametrize("B,N,K,S,P,Ci,Co,ci_t,co_t,sp,u",
                         RAGGED_TILE_SWEEP)
def test_dconv_filtergrad_ragged_tiles(rng, B, N, K, S, P, Ci, Co, ci_t,
                                       co_t, sp, u):
    """Explicitly pinned tilings with ragged channel/spatial remainders
    (pad-then-slice paths) still match the oracle at B > 1."""
    O = (N + 2 * P - K) // S + 1
    x = jnp.asarray(rng.normal(size=(B, N, N, Ci)), jnp.float32)
    dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
    dw = dconv_filter_grad_pallas(x, dy, stride=(S, S), padding=(P, P),
                                  k=(K, K), cin_tile=ci_t, cout_tile=co_t,
                                  spatial_tile=sp, tap_unroll=u,
                                  interpret=True)
    want = ref.dconv_filter_grad_ref(x, dy, stride=(S, S), padding=(P, P),
                                     k=(K, K))
    assert_allclose(dw, want, rtol=1e-4, atol=1e-4)


def test_dconv_filtergrad_bf16(rng):
    B, N, K, S, Ci, Co = 2, 9, 3, 2, 4, 4
    O = (N - K) // S + 1
    x = jnp.asarray(rng.normal(size=(B, N, N, Ci)), jnp.bfloat16)
    dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.bfloat16)
    dw = dconv_filter_grad_pallas(x, dy, stride=(S, S), padding=(0, 0),
                                  k=(K, K), interpret=True)
    want = ref.dconv_filter_grad_ref(x, dy, stride=(S, S), padding=(0, 0),
                                     k=(K, K))
    assert_allclose(dw, want, rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# dconv_forward (fused zero-free dilated forward conv)
# ---------------------------------------------------------------------------

DFWD_SWEEP = [
    # (B, N, K, S, P, D, Ci, Co)
    (1, 13, 3, 1, 2, 2, 3, 4),       # atrous same-padding
    (2, 15, 3, 1, 4, 4, 2, 3),       # d=4 same-padding
    (1, 14, 3, 2, 1, 2, 3, 2),       # stride 2 + dilation 2
    (2, 17, 2, 3, 0, 4, 2, 2),       # non-exact fit
    (1, 12, 1, 2, 0, 3, 2, 2),       # pointwise: K_eff == 1
    (1, 13, 3, 1, 2, 2, 5, 130),     # Cout > default tile
    (1, 9, 3, 1, 2, 2, 130, 3),      # Cin > default tile (x block tiled)
]


@pytest.mark.parametrize("B,N,K,S,P,D,Ci,Co", DFWD_SWEEP)
def test_dconv_forward_sweep(rng, B, N, K, S, P, D, Ci, Co):
    x = jnp.asarray(rng.normal(size=(B, N, N, Ci)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
    y = ops.dconv_forward(x, w, stride=(S, S), padding=(P, P),
                          dilation=(D, D))
    want = ref.dconv_forward_ref(x, w, stride=(S, S), padding=(P, P),
                                 dilation=(D, D))
    assert_allclose(y, want, rtol=1e-4, atol=1e-4)


def test_dconv_forward_cin_tiled(rng):
    """The padded-input block no longer spans full channel depth: with
    Cin > cin_tile the grid gains a sequential Cin-accumulation axis and
    the x/w blocks are capped at the tile -- and the output still matches
    the oracle (fp32 accumulation across (Cin-tile, tap) steps)."""
    B, N, K, S, P, D, Ci, Co, tile = 2, 11, 3, 1, 2, 2, 20, 12, 8
    x = jnp.asarray(rng.normal(size=(B, N, N, Ci)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
    fn = lambda x_, w_: dconv_forward_pallas(
        x_, w_, stride=(S, S), padding=(P, P), dilation=(D, D),
        cin_tile=tile, cout_tile=tile, tap_unroll=1, interpret=True)
    grids = _pallas_grids(fn, x, w)
    assert len(grids) == 1
    # grid (B, Cout_t, Cin_t, T): batch leads, taps innermost, and a
    # sequential Cin axis of ceil(Ci/tile) blocks.
    assert grids[0] == (B, -(-Co // tile), -(-Ci // tile), K * K), grids[0]
    blocks = pallas_block_shapes(fn, x, w)[0]
    x_block, w_block, out_block = blocks
    assert x_block[-1] == tile, blocks         # padded input: Cin tile
    assert w_block[-2:] == (tile, tile), blocks
    y = fn(x, w)
    want = ref.dconv_forward_ref(x, w, stride=(S, S), padding=(P, P),
                                 dilation=(D, D))
    assert_allclose(y, want, rtol=1e-4, atol=1e-4)


def test_dconv_forward_bf16(rng):
    B, N, K, D, Ci, Co = 1, 11, 3, 2, 4, 4
    x = jnp.asarray(rng.normal(size=(B, N, N, Ci)), jnp.bfloat16)
    w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.bfloat16)
    y = dconv_forward_pallas(x, w, stride=(1, 1), padding=(2, 2),
                             dilation=(2, 2), interpret=True)
    assert y.dtype == jnp.bfloat16
    want = ref.dconv_forward_ref(x, w, stride=(1, 1), padding=(2, 2),
                                 dilation=(2, 2))
    assert_allclose(y, want, rtol=5e-2, atol=5e-2)


# ---------------------------------------------------------------------------
# ops wrappers
# ---------------------------------------------------------------------------

def test_ops_import_does_not_initialize_backend():
    """The interpret/compiled decision is resolved per call, NOT at
    import: importing `repro.kernels.ops` must not force jax backend
    initialization (the old module-level `_INTERPRET` constant did, and
    went stale if the device set changed after import)."""
    import subprocess
    import sys
    code = (
        "import repro.kernels.ops\n"
        "try:\n"
        "    from jax._src.xla_bridge import _backends\n"
        "except ImportError:   # private jax surface moved: can't probe\n"
        "    print('SKIP')\n"
        "    raise SystemExit(0)\n"
        "assert not _backends, list(_backends)\n"
        "print('OK')\n")
    proc = subprocess.run([sys.executable, "-c", code],
                          capture_output=True, text=True)
    assert proc.returncode == 0 and ("OK" in proc.stdout
                                     or "SKIP" in proc.stdout), (
        proc.stdout, proc.stderr)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_SWEEP = [
    # (B, Sq, Sk, Hq, Hk, D, causal, bq, bk)
    (2, 64, 64, 4, 2, 32, True, 32, 32),
    (1, 128, 128, 8, 8, 64, True, 64, 32),
    (2, 48, 96, 4, 1, 32, True, 16, 32),    # MQA, decode-style suffix
    (1, 33, 70, 8, 2, 16, False, 32, 32),   # ragged, non-causal
    (1, 1, 40, 4, 4, 32, True, 8, 16),      # single-token decode
    (2, 70, 70, 2, 2, 128, True, 32, 64),   # head_dim 128
]


@pytest.mark.parametrize("B,Sq,Sk,Hq,Hk,D,causal,bq,bk", ATTN_SWEEP)
def test_flash_attention_sweep(rng, B, Sq, Sk, Hq, Hk, D, causal, bq, bk):
    q = jnp.asarray(rng.normal(size=(B, Sq, Hq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Sk, Hk, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Sk, Hk, D)), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=causal, blk_q=bq,
                                 blk_k=bk, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    assert_allclose(out, want, rtol=2e-5, atol=2e-5)


def test_flash_attention_bf16(rng):
    B, S, H, D = 2, 64, 4, 32
    q = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(B, S, H, D)), jnp.bfloat16)
    out = flash_attention_pallas(q, k, v, blk_q=32, blk_k=32,
                                 interpret=True)
    want = ref.flash_attention_ref(q, k, v)
    assert out.dtype == jnp.bfloat16
    assert_allclose(out, want, rtol=5e-2, atol=5e-2)


@settings(max_examples=15, deadline=None)
@given(sq=st.integers(1, 40), extra=st.integers(0, 40),
       hk=st.sampled_from([1, 2, 4]), g=st.sampled_from([1, 2]),
       causal=st.booleans())
def test_flash_attention_property(sq, extra, hk, g, causal):
    """Any (Sq <= Sk, GQA group, mask) combination matches the oracle."""
    rng = np.random.default_rng(sq * 1000 + extra * 10 + hk + g)
    sk = sq + extra
    B, D = 1, 16
    q = jnp.asarray(rng.normal(size=(B, sq, hk * g, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, sk, hk, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, sk, hk, D)), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=causal, blk_q=16,
                                 blk_k=16, interpret=True)
    want = ref.flash_attention_ref(q, k, v, causal=causal)
    assert_allclose(out, want, rtol=3e-5, atol=3e-5)


def test_tconv_fully_unrolled_skips_padding_slots(rng):
    """Backported static padding-slot skip (the fused backward kernel's
    shared (phase, slot) -> filter-tap validity test): at full
    (phase, tap) unroll every slot index is a python int, so slots whose
    flipped tap kx = a + (KP-1-u)*period falls outside the KxK filter
    are skipped outright -- the kernel body carries exactly Kh*Kw
    matmuls, not T*TK (the zero-padded slots of ragged phases never
    become MACs).  S=2, K=3: 4 phases x 4 packed slots = 16 slots but
    only 9 real taps."""
    from conftest import walk_eqns
    B, O, K, S, Ci, Co = 1, 4, 3, 2, 4, 4
    dy = jnp.asarray(rng.normal(size=(B, O, O, Co)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, K, Ci, Co)), jnp.float32)
    N = S * (O - 1) + K
    fn = lambda dy_, w_: tconv_fused_pallas(
        dy_, w_, stride=(S, S), padding=(0, 0), n_out=(N, N),
        tap_unroll=4, phase_unroll=4, cin_tile=Ci, cout_tile=Co,
        interpret=True)
    jaxpr = jax.make_jaxpr(fn)(dy, w)
    dots = [e for e in walk_eqns(jaxpr.jaxpr)
            if e.primitive.name == "dot_general"]
    assert len(dots) == K * K, len(dots)         # 9, not 16
    # ... and the skip changes nothing numerically.
    assert_allclose(fn(dy, w),
                    ref.tconv_phase_ref(dy, w, stride=(S, S),
                                        padding=(0, 0), n_out=(N, N)),
                    rtol=1e-4, atol=1e-4)
