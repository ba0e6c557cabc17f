"""Compile the main paths' kernels for a TPU v5e that is described, not
attached.

The TPU compiler is installed with JAX, so a program can be compiled for a
chip described by its topology with no chip present.  That catches what
interpret mode cannot (block shapes the Mosaic lowering refuses, programs
that do not fit) at no chip time.  Nothing here runs, so these tests say
nothing about results or speed.

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, and the
test workers all import this file.  Keep every such compile in this one
file, so that one worker loads the library.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ckpt_delta
from repro.kernels.event_step import N_F, N_I, event_step

# An xlstm-125m embedding leaf (vocab x d_model) and a 2048 x 8192 leaf.
DELTA_SHAPES = [(50304, 768), (2048, 8192)]
STEP_KW = dict(c=600.0, cp=600.0, d=60.0, r=600.0, time_base=4.8e6)


@pytest.fixture(scope="module")
def topo():
    # Keep the TPU compiler's logs out of the shared temporary directory.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off:
    a program compiled for a described chip cannot be read back here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_topology_is_a_v5e(topo):
    assert topo.devices[0].device_kind == "TPU v5 lite"
    assert len(topo.devices) == 4


@pytest.mark.parametrize("lanes", [4800, 65536])
def test_event_step_ref_compiles_float64(one_chip, lanes):
    """The engine's default event step, on float64 lane state (emulated
    by the TPU), at the paper grid's 4,800 lanes and a 2^16 chunk."""
    step = functools.partial(event_step, impl="ref", **STEP_KW)
    with jax.enable_x64(True):
        fs = _struct((N_F, lanes), jnp.float64, one_chip)
        is_ = _struct((N_I, lanes), jnp.int32, one_chip)
        compiled = jax.jit(step).lower(fs, is_).compile()
    out_fs, out_is = compiled.out_info
    assert out_fs.shape == (N_F, lanes) and out_fs.dtype == jnp.float64
    assert out_is.shape == (N_I, lanes)


@pytest.mark.parametrize("shape", DELTA_SHAPES)
def test_quantize_delta_kernel_compiles(one_chip, shape):
    cur = _struct(shape, jnp.float32, one_chip)
    fn = functools.partial(ckpt_delta.quantize_delta_pallas, interpret=False)
    compiled = jax.jit(fn).lower(cur, cur).compile()
    assert "tpu_custom_call" in compiled.as_text()
    q, s = compiled.out_info
    n_blocks = -(-shape[0] * shape[1] // ckpt_delta.BLOCK)
    assert q.shape == (n_blocks, ckpt_delta.BLOCK) and q.dtype == jnp.int8
    assert s.shape == (n_blocks,) and s.dtype == jnp.float32


@pytest.mark.parametrize("shape", DELTA_SHAPES)
def test_dequantize_delta_kernel_compiles(one_chip, shape):
    n_blocks = -(-shape[0] * shape[1] // ckpt_delta.BLOCK)
    q = _struct((n_blocks, ckpt_delta.BLOCK), jnp.int8, one_chip)
    s = _struct((n_blocks,), jnp.float32, one_chip)
    base = _struct(shape, jnp.float32, one_chip)
    fn = functools.partial(ckpt_delta.dequantize_delta_pallas,
                           interpret=False)
    compiled = jax.jit(fn).lower(q, s, base).compile()
    assert "tpu_custom_call" in compiled.as_text()
    (out,) = jax.tree.leaves(compiled.out_info)
    assert out.shape == shape and out.dtype == jnp.float32
