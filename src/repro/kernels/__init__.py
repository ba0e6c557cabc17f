"""Pallas TPU kernels for the framework's compute hot-spots.

  flash_attention   prefill/train attention (online softmax, GQA, windows)
  decode_attention  single-token KV-cache attention (serving)
  ckpt_delta        int8 delta quantization for proactive checkpoints

Each kernel ships with a pure-jnp oracle in ref.py; ops.py is the public
dispatching API.  The kernels target the TPU (BlockSpec VMEM tiling,
MXU-aligned tiles) and compile by default; interpret mode runs only where a
caller asks for it (``interpret=True``, ``impl="pallas_interpret"``), as the
CPU tests do.  ``tests/test_tpu_compile.py`` compiles the checkpoint-delta
kernels for a described v5e.
"""

from . import ckpt_delta, decode_attention, flash_attention, ops, ref

__all__ = ["ckpt_delta", "decode_attention", "flash_attention", "ops", "ref"]
