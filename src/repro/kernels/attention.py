"""Pallas TPU kernel: blockwise (flash) causal GQA attention.

The serving/prefill hot-spot of every attention arch in the pool.  One
`pallas_call` runs the full online-softmax recurrence:

  grid (B, Hq, Sq/blk_q, Sk/blk_k), kv innermost (sequential on TPU), with
  the running max `m`, normalizer `l` and the fp32 output accumulator kept
  in VMEM scratch across kv steps -- the Pallas equivalent of the flash
  attention SRAM state.

BlockSpec tiling: per grid step the kernel holds
  q block   (1, blk_q, 1, D)
  k/v block (1, blk_k, 1, D)     -- GQA: Hq head h reads Hk head h//g
  out block (1, blk_q, 1, D)     -- written once, on the last kv step
so VMEM holds O(blk_q*D + blk_k*D) per step regardless of Sk; blk_q =
blk_k = 128 aligns both matmuls ((blk_q x D) @ (D x blk_k) and
(blk_q x blk_k) @ (blk_k x D)) to the MXU.

Causal masking uses absolute positions (q_offset = Sk - Sq supports
decode-style suffix queries).  Fully-masked kv blocks are skipped via
pl.when on the block index -- the flash-attention "causal block skip",
which halves the schedule for the prefill cells.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, blk_q: int, blk_k: int, causal: bool,
                  sq: int, sk: int, q_offset: int):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = q_offset + qi * blk_q + jax.lax.iota(jnp.int32, blk_q)
    k_pos = ki * blk_k + jax.lax.iota(jnp.int32, blk_k)
    # Causal block skip: this kv block contributes iff its first key is
    # <= the last query position (and inside the real sequence).
    live = (k_pos[0] <= q_pos[-1]) if causal else True

    @pl.when(live)
    def _step():
        q = q_ref[0, :, 0, :].astype(jnp.float32) * scale       # (bq, D)
        k = k_ref[0, :, 0, :].astype(jnp.float32)               # (bk, D)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        mask = k_pos[None, :] < sk
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        s = jnp.where(mask, s, NEG_INF)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_prev * corr + p.sum(axis=1)
        m_ref[...] = m_new
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, :, 0, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "blk_q", "blk_k",
                                             "interpret", "q_offset"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, blk_q: int = 128,
                           blk_k: int = 128, q_offset: int | None = None,
                           interpret: bool) -> jax.Array:
    """q (B,Sq,Hq,D), k/v (B,Sk,Hk,D), Hq % Hk == 0 -> (B,Sq,Hq,D)."""
    B, Sq, Hq, D = q.shape
    _, Sk, Hk, _ = k.shape
    g = Hq // Hk
    scale = D ** -0.5
    off = (Sk - Sq) if q_offset is None else q_offset
    bq, bk = min(blk_q, Sq), min(blk_k, Sk)
    nq, nk = -(-Sq // bq), -(-Sk // bk)
    if Sq % bq:
        q = jnp.pad(q, ((0, 0), (0, nq * bq - Sq), (0, 0), (0, 0)))
    if Sk % bk:
        k = jnp.pad(k, ((0, 0), (0, nk * bk - Sk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, nk * bk - Sk), (0, 0), (0, 0)))
    kern = functools.partial(_flash_kernel, scale=scale, blk_q=bq,
                             blk_k=bk, causal=causal, sq=Sq, sk=Sk,
                             q_offset=off)
    out = pl.pallas_call(
        kern,
        grid=(B, Hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, 1, D), lambda b, h, i, j: (b, i, h, 0)),
            pl.BlockSpec((1, bk, 1, D),
                         lambda b, h, i, j, g=g: (b, j, h // g, 0)),
            pl.BlockSpec((1, bk, 1, D),
                         lambda b, h, i, j, g=g: (b, j, h // g, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, 1, D),
                               lambda b, h, i, j: (b, i, h, 0)),
        out_shape=jax.ShapeDtypeStruct((B, nq * bq, Hq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),       # running max m
            pltpu.VMEM((bq,), jnp.float32),       # normalizer l
            pltpu.VMEM((bq, D), jnp.float32),     # fp32 out accumulator
        ],
        interpret=interpret,
    )(q, k, v)
    return out[:, :Sq]
