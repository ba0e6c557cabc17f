"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


# -- flash attention -------------------------------------------------------------

FLASH_CASES = [
    # (b, sq, skv, h, kv, hd, causal, window, q_offset)
    (2, 128, 128, 4, 4, 64, True, 0, 0),      # MHA causal
    (2, 128, 128, 4, 2, 64, True, 0, 0),      # GQA
    (1, 256, 256, 8, 1, 64, True, 0, 0),      # MQA
    (1, 128, 128, 4, 2, 64, True, 64, 0),     # sliding window
    (2, 128, 256, 4, 2, 32, True, 0, 128),    # continuation (q_offset)
    (2, 128, 128, 4, 4, 64, False, 0, 0),     # bidirectional (encoder)
    (1, 64, 64, 2, 2, 128, True, 0, 0),       # head_dim 128
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    b, sq, skv, h, kv, hd, causal, window, q_offset = case
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (b, sq, h, hd), dtype)
    k = rand(ks[1], (b, skv, kv, hd), dtype)
    v = rand(ks[2], (b, skv, kv, hd), dtype)
    out_ref = ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                      q_offset=q_offset)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              q_offset=q_offset, impl="pallas_interpret",
                              bq=64, bk=64)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(out_ref, np.float32),
                               atol=tol, rtol=tol)


def test_flash_attention_odd_blocks():
    """Block sizes that do not divide seq fall back to smaller divisors."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = rand(ks[0], (1, 96, 2, 32), jnp.float32)
    k = rand(ks[1], (1, 96, 2, 32), jnp.float32)
    v = rand(ks[2], (1, 96, 2, 32), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=True, impl="pallas_interpret",
                              bq=64, bk=64)
    out_ref = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-6)


def test_flash_matches_model_reference_path():
    """The model's chunked_attention agrees with the kernel oracle."""
    from repro.models.layers import chunked_attention
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = rand(ks[0], (2, 128, 8, 64), jnp.float32)
    k = rand(ks[1], (2, 128, 2, 64), jnp.float32)
    v = rand(ks[2], (2, 128, 2, 64), jnp.float32)
    a = chunked_attention(q, k, v, causal=True, q_chunk=32, kv_chunk=32)
    b = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)


# -- decode attention ------------------------------------------------------------

DECODE_CASES = [
    # (b, s, h, kv, hd, window, length)
    (2, 256, 8, 2, 64, 0, 200),
    (2, 256, 8, 8, 64, 0, 17),
    (3, 128, 10, 1, 32, 64, 100),   # ring buffer (recurrentgemma-like GQA)
    (1, 512, 4, 4, 128, 0, 512),
    (2, 128, 4, 2, 64, 128, 40),    # window larger than filled prefix
]


@pytest.mark.parametrize("case", DECODE_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_matches_ref(case, dtype):
    b, s, h, kv, hd, window, length = case
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = rand(ks[0], (b, 1, h, hd), dtype)
    kc = rand(ks[1], (b, s, kv, hd), dtype)
    vc = rand(ks[2], (b, s, kv, hd), dtype)
    lengths = jnp.full((b,), length, jnp.int32)
    out_ref = ref.decode_attention_ref(q, kc, vc, lengths, window=window)
    out = ops.decode_attention(q, kc, vc, lengths, window=window,
                               impl="pallas_interpret", bk=64)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(out_ref, np.float32),
                               atol=tol, rtol=tol)


def test_decode_attention_per_batch_lengths():
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = rand(ks[0], (3, 1, 4, 32), jnp.float32)
    kc = rand(ks[1], (3, 128, 2, 32), jnp.float32)
    vc = rand(ks[2], (3, 128, 2, 32), jnp.float32)
    lengths = jnp.array([1, 64, 128], jnp.int32)
    out_ref = ref.decode_attention_ref(q, kc, vc, lengths)
    out = ops.decode_attention(q, kc, vc, lengths,
                               impl="pallas_interpret", bk=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_ref),
                               atol=2e-6)


# -- ckpt delta -------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1000, 37), (256,), (8, 8, 8),
                                   (4096, 16), (123,)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quantize_delta_matches_ref(shape, dtype):
    ks = jax.random.split(jax.random.PRNGKey(5), 2)
    base = rand(ks[0], shape, dtype)
    cur = base + 0.01 * rand(ks[1], shape, dtype).astype(dtype)
    q_ref, s_ref = ref.quantize_delta_ref(cur, base)
    q, s = ops.quantize_delta(cur, base, impl="pallas_interpret")
    # Fused divide-vs-reciprocal rounding may flip exact .5 ties by +-1 on a
    # tiny fraction of elements; anything more is a real bug.
    diff = np.abs(np.asarray(q, np.int32) - np.asarray(q_ref, np.int32))
    assert diff.max() <= 1
    assert (diff > 0).mean() < 2e-3
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref), rtol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_delta_roundtrip_error_bound(dtype):
    """Reconstruction error <= scale/2 = absmax/254 per block."""
    ks = jax.random.split(jax.random.PRNGKey(6), 2)
    base = rand(ks[0], (513, 17), dtype)
    cur = base + 0.05 * rand(ks[1], (513, 17), dtype).astype(dtype)
    q, s = ops.quantize_delta(cur, base, impl="pallas_interpret")
    rec = ops.dequantize_delta(q, s, base, impl="pallas_interpret")
    delta = np.abs(np.asarray(cur, np.float32) - np.asarray(rec, np.float32))
    bound = float(np.max(np.asarray(s))) * 0.5 + 1e-2 * (
        dtype == jnp.bfloat16)
    assert delta.max() <= bound + 1e-7


def test_quantize_zero_delta():
    x = jnp.ones((512,), jnp.float32)
    q, s = ops.quantize_delta(x, x, impl="pallas_interpret")
    assert int(jnp.abs(q.astype(jnp.int32)).max()) == 0
    rec = ops.dequantize_delta(q, s, x, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(rec), np.asarray(x))


# -- compiled-kernel dispatch -------------------------------------------------

def test_kernel_entry_points_compile_by_default():
    """Interpret mode happens only where a caller asks for it by name."""
    import inspect

    from repro.kernels import ckpt_delta, decode_attention, flash_attention
    for fn in (ckpt_delta.quantize_delta_pallas.__wrapped__,
               ckpt_delta.dequantize_delta_pallas,
               flash_attention.flash_attention_pallas.__wrapped__,
               decode_attention.decode_attention_pallas.__wrapped__):
        assert inspect.signature(fn).parameters["interpret"].default is False


# -- kernels wired into the model (attn_impl config knob) -------------------

@pytest.mark.parametrize("arch", ["llama3.2-1b", "recurrentgemma-2b"])
def test_model_level_kernel_parity(arch):
    """forward_train/decode with Pallas(interpret) == reference path."""
    import dataclasses
    from repro.configs import REGISTRY
    from repro.configs.base import InputShape
    from repro.models import (decode_step, forward_train, init_params,
                              make_batch, prefill)
    cfg = dataclasses.replace(REGISTRY[arch].reduced(), dtype="float32",
                              remat=False)
    params, _ = init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, InputShape("t", 32, 2, "train"),
                       jax.random.PRNGKey(1))
    cfgk = dataclasses.replace(cfg, attn_impl="pallas_interpret")
    l_ref, _ = forward_train(cfg, params, batch)
    l_pal, _ = forward_train(cfgk, params, batch)
    np.testing.assert_allclose(np.asarray(l_pal), np.asarray(l_ref),
                               atol=1e-3)
    _, cache = prefill(cfg, params, batch, cache_len=40)
    _, cachek = prefill(cfgk, params, batch, cache_len=40)
    tok = batch["tokens"][:, -1]
    lg_ref, _ = decode_step(cfg, params, tok, cache)
    lg_pal, _ = decode_step(cfgk, params, tok, cachek)
    np.testing.assert_allclose(np.asarray(lg_pal), np.asarray(lg_ref),
                               atol=1e-3)
