"""Pallas TPU kernel: flash attention (prefill/train hot-spot).

Online-softmax attention with GQA, causal and sliding-window masking, tiled
for the TPU memory hierarchy:

  * grid = (B*H, Sq/BQ, Skv/BK); the kv axis is the innermost *sequential*
    dimension ("arbitrary"), so the running (m, l, acc) state lives in VMEM
    scratch across kv iterations — the standard TPU flash schedule;
  * q tiles (BQ, hd) and k/v tiles (BK, hd) stream HBM -> VMEM; the (BQ, BK)
    score matrix hits the MXU with both dims multiples of 128 by default;
  * GQA is expressed in the BlockSpec index maps: query head h reads kv head
    h // (H // KV) — no head replication in HBM.

The contract is ``ref.flash_attention_ref``; tests sweep (seq, heads, kv,
window, dtype) in interpret mode, which a caller asks for with
``interpret=True``.  The default compiles the kernel for the TPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, q_offset: int,
            bq: int, bk: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32) * scale            # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                    # (bk, hd)
    v = v_ref[0].astype(jnp.float32)                    # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)

    qi = pl.program_id(1)
    q_pos = q_offset + qi * bq + jax.lax.broadcasted_iota(
        jnp.int32, (bq, bk), 0)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= q_pos - k_pos < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] \
        + jax.lax.dot(p.astype(v.dtype), v)
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _fin():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0] = out.astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "bq", "bk", "interpret"))
def flash_attention_pallas(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           q_offset: int = 0, bq: int = 128, bk: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q (B,Sq,H,hd); k,v (B,Skv,KV,hd) -> (B,Sq,H,hd)."""
    b, sq, h, hd = q.shape
    skv, kv = k.shape[1], k.shape[2]
    g = h // kv
    bq = min(bq, sq)
    bk = min(bk, skv)
    while sq % bq:
        bq -= 1
    while skv % bk:
        bk -= 1
    scale = 1.0 / math.sqrt(hd)

    qr = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(b * kv, skv, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(b * kv, skv, hd)

    grid = (b * h, sq // bq, skv // bk)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def kv_map(bh, qi, ki):
        # GQA: query head (bh % h) reads kv head (bh % h) // g.
        return ((bh // h) * kv + (bh % h) // g, ki, 0)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, bq=bq, bk=bk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, hd), q_map),
            pl.BlockSpec((1, bk, hd), kv_map),
            pl.BlockSpec((1, bk, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
