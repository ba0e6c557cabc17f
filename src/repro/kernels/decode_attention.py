"""Pallas TPU kernel: single-token GQA decode attention (serving hot-spot).

Decode attention is memory-bound: the whole KV cache streams through VMEM
once per generated token while the compute is a skinny (g x hd) x (hd x BK)
matmul per kv head.  Tiling:

  * grid = (B, KV, S/BK): batch and kv-head parallel, cache-sequence axis
    sequential with (m, l, acc) running state in VMEM scratch;
  * the q block is the *group* of g = H/KV query heads that share one kv
    head — they ride along in a single (g, hd) VMEM tile and amortize each
    cache tile read g ways (the GQA bandwidth win, explicit in the tiling);
  * ``length`` (B,) masks the valid cache prefix (ring-buffer semantics for
    sliding-window archs: valid = min(length, window) entries).

Contract: ``ref.decode_attention_ref``; swept in interpret mode by tests
(``interpret=True``).  The default compiles the kernel for the TPU.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


__all__ = ["decode_attention_pallas"]

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            window: int, scale: float, bk: int):
    si = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(si == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (g, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)                  # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (g, bk)

    length = len_ref[0]
    lim = jnp.minimum(length, window) if window else length
    k_idx = si * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_idx < lim, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot(p, v)
    m_scr[...] = m_new

    @pl.when(si == ns - 1)
    def _fin():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("window", "bk", "interpret"))
def decode_attention_pallas(q: jax.Array, k_cache: jax.Array,
                            v_cache: jax.Array, length: jax.Array, *,
                            window: int = 0, bk: int = 512,
                            interpret: bool = False) -> jax.Array:
    """q (B,1,H,hd); caches (B,S,KV,hd); length (B,) -> (B,1,H,hd)."""
    b, _, h, hd = q.shape
    s, kv = k_cache.shape[1], k_cache.shape[2]
    g = h // kv
    bk = min(bk, s)
    while s % bk:
        bk -= 1
    scale = 1.0 / math.sqrt(hd)

    qr = q.reshape(b, kv, g, hd)
    kr = k_cache.transpose(0, 2, 1, 3)                   # (B, KV, S, hd)
    vr = v_cache.transpose(0, 2, 1, 3)

    grid = (b, kv, s // bk)
    kernel = functools.partial(_kernel, window=window, scale=scale, bk=bk)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1,), lambda bi, ki, si: (bi,)),
            pl.BlockSpec((1, 1, g, hd), lambda bi, ki, si: (bi, ki, 0, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda bi, ki, si: (bi, ki, si, 0)),
            pl.BlockSpec((1, 1, bk, hd), lambda bi, ki, si: (bi, ki, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda bi, ki, si: (bi, ki, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kv, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g,), jnp.float32),
            pltpu.VMEM((g, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(length.astype(jnp.int32), qr, kr, vr)
    return out.reshape(b, 1, h, hd)
