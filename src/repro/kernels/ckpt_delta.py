"""Pallas TPU kernel: blockwise-int8 delta quantization for proactive ckpts.

This is the compute hot-spot of the *proactive* checkpoint path (paper cost
C_p): the TrainState delta vs the last full checkpoint is quantized to int8
with per-block absmax scales before hitting storage, cutting the payload
~4x and therefore C_p ~4x below C (the TPU realization of the paper's cheap
localized proactive checkpoints).

The op is purely memory-bound (one read of cur+base, one write of q), so the
kernel is a streaming VMEM pipeline: tiles of (TILE_ROWS, BLOCK) flow
HBM -> VMEM, the VPU does abs/max/round, and int8 rows flow back.  BLOCK is
the quantization granularity AND the lane dimension (256 = 2 VREG lanes);
TILE_ROWS=8 matches the sublane count for fp32.  Per-block scales travel as
a ``(blocks, 1)`` column, since the TPU lowering refuses rank-1 blocks
narrower than 128.

The kernels compile for the TPU by default; tests validate them against
``ref.quantize_delta_ref`` in interpret mode (``interpret=True``) and compile
them for a described v5e (``tests/test_tpu_compile.py``).  The public entry
points accept any-shape inputs and handle padding/reshaping around the kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref as _ref

__all__ = ["quantize_delta", "dequantize_delta", "quantize_delta_pallas",
           "dequantize_delta_pallas"]

BLOCK = 256
TILE_ROWS = 8


def _quant_kernel(cur_ref, base_ref, q_ref, s_ref):
    """One (TILE_ROWS, BLOCK) tile: delta -> absmax scale -> int8."""
    delta = cur_ref[...].astype(jnp.float32) - base_ref[...].astype(jnp.float32)
    absmax = jnp.max(jnp.abs(delta), axis=1, keepdims=True)    # (rows, 1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(delta / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale.astype(jnp.float32)


def _dequant_kernel(q_ref, s_ref, base_ref, out_ref):
    delta = q_ref[...].astype(jnp.float32) * s_ref[...]
    out_ref[...] = (base_ref[...].astype(jnp.float32) + delta
                    ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def quantize_delta_pallas(cur: jax.Array, base: jax.Array, *,
                          block: int = BLOCK, interpret: bool = False
                          ) -> tuple[jax.Array, jax.Array]:
    """Pallas path. Returns (q (n_blocks, block) int8, scales (n_blocks,))."""
    blocks_c, _ = _ref._pad_blocks(
        cur.astype(jnp.float32) - 0.0, block)  # reshape only
    blocks_b, _ = _ref._pad_blocks(base.astype(jnp.float32) - 0.0, block)
    n = blocks_c.shape[0]
    rows = TILE_ROWS
    pad_rows = (-n) % rows
    if pad_rows:
        blocks_c = jnp.pad(blocks_c, ((0, pad_rows), (0, 0)))
        blocks_b = jnp.pad(blocks_b, ((0, pad_rows), (0, 0)))
    grid = (blocks_c.shape[0] // rows,)
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, block), lambda i: (i, 0)),
            pl.BlockSpec((rows, block), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((rows, block), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(blocks_c.shape, jnp.int8),
            jax.ShapeDtypeStruct((blocks_c.shape[0], 1), jnp.float32),
        ],
        interpret=interpret,
    )(blocks_c, blocks_b)
    return q[:n], s[:n, 0]


@functools.partial(jax.jit,
                   static_argnames=("block", "interpret"))
def _dequant_blocks_pallas(q: jax.Array, s: jax.Array, base_blocks: jax.Array,
                           *, block: int, interpret: bool) -> jax.Array:
    n = q.shape[0]
    rows = TILE_ROWS
    pad_rows = (-n) % rows
    if pad_rows:
        q = jnp.pad(q, ((0, pad_rows), (0, 0)))
        s = jnp.pad(s, (0, pad_rows))
        base_blocks = jnp.pad(base_blocks, ((0, pad_rows), (0, 0)))
    grid = (q.shape[0] // rows,)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((rows, block), lambda i: (i, 0)),
            pl.BlockSpec((rows, 1), lambda i: (i, 0)),
            pl.BlockSpec((rows, block), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((rows, block), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
        interpret=interpret,
    )(q, s[:, None], base_blocks)
    return out[:n]


def dequantize_delta_pallas(q: jax.Array, scales: jax.Array, base: jax.Array,
                            *, block: int = BLOCK, interpret: bool = False
                            ) -> jax.Array:
    base_blocks, _ = _ref._pad_blocks(base.astype(jnp.float32) - 0.0, block)
    out = _dequant_blocks_pallas(q, scales, base_blocks, block=block,
                                 interpret=interpret)
    return out.reshape(-1)[: base.size].reshape(base.shape).astype(base.dtype)


# -- public entry points (manager uses these; ref fallback on CPU) ------------

def quantize_delta(cur: jax.Array, base: jax.Array, *, block: int = BLOCK,
                   use_pallas: bool = False) -> tuple[jax.Array, jax.Array]:
    if use_pallas:
        return quantize_delta_pallas(cur, base, block=block)
    return _ref.quantize_delta_ref(cur, base, block=block)


def dequantize_delta(q: jax.Array, scales: jax.Array, base: jax.Array, *,
                     block: int = BLOCK, use_pallas: bool = False) -> jax.Array:
    if use_pallas:
        return dequantize_delta_pallas(q, scales, base, block=block)
    return _ref.dequantize_delta_ref(q, scales, base, block=block)
