"""Checkpoint manager: periodic (full) + proactive (delta) checkpoints.

This is the framework realization of the paper's two checkpoint costs:

  * C   — a *full* checkpoint: every leaf of the TrainState (params,
          optimizer moments, data cursor, RNG) serialized to stable storage,
          double-buffered (the previous checkpoint is only dropped once the
          new one is durable — a fault mid-checkpoint must not destroy the
          last good state, which is exactly the paper's model where a fault
          during a checkpoint rolls back to the previous one).
  * C_p — a *proactive* checkpoint taken on a fault prediction: a blockwise
          int8-quantized delta against the last full checkpoint
          (Check-N-Run-style incremental+quantized checkpointing).  Payload
          is ~4x smaller than a bf16 full state, realizing the paper's
          C_p < C scenario [§2.2, citing Zheng et al.'s localized cheap
          proactive checkpoints].  Restoring replays base + delta.

Cost model: with per-chip checkpoint bandwidth ``bw`` and per-chip shard
bytes ``s``, C = s / bw (each chip writes its own shard concurrently — the
coordinated-checkpointing cost is per-shard, not global).  ``measure=True``
instead times the actual host serialization, for CPU-scale examples.

The quantize/dequantize hot loop has a Pallas kernel
(``repro.kernels.ckpt_delta``); the manager calls its pure-jnp reference on
every platform.
"""

from __future__ import annotations

import dataclasses
import json
import os
import re
import shutil
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels import ckpt_delta as _delta

__all__ = ["DELTA_RATIO_PRIOR", "SaveInfo", "CheckpointManager",
           "state_bytes", "modeled_costs_from_bytes"]

# Prior payload ratio of proactive (int8 delta + per-block scales) vs full
# (bf16/fp32) checkpoints, used until a manager has measured its own saves.
DELTA_RATIO_PRIOR = 0.27


def modeled_costs_from_bytes(nbytes: float, *, bandwidth: float,
                             n_shards: int = 1,
                             delta_ratio: float = DELTA_RATIO_PRIOR,
                             ) -> tuple[float, float]:
    """(C, C_p) in seconds from a state size in bytes (no state needed).

    The pure form of :meth:`CheckpointManager.modeled_costs`, for planners
    that know the state size analytically (e.g. fleet jobs sized from
    ``ModelConfig.param_count``) without instantiating any state.
    """
    b = nbytes / max(1, n_shards)
    return b / bandwidth, delta_ratio * b / bandwidth


def state_bytes(state: Any) -> int:
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(state))


def _leaf_names(tree: Any) -> list[str]:
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [jax.tree_util.keystr(p) for p, _ in paths]


def _encode(leaf: np.ndarray) -> np.ndarray:
    """npz-safe encoding: bfloat16 (unknown to numpy) stored as uint16 bits."""
    if leaf.dtype == jnp.bfloat16:
        return leaf.view(np.uint16)
    return leaf


def _decode(arr: np.ndarray, target_dtype) -> jax.Array:
    if target_dtype == jnp.bfloat16 and arr.dtype == np.uint16:
        return jnp.asarray(arr.view(jnp.bfloat16))
    return jnp.asarray(arr).astype(target_dtype)


@dataclasses.dataclass(frozen=True)
class SaveInfo:
    step: int
    kind: str          # "full" | "proactive"
    bytes: int         # serialized payload size
    seconds: float     # measured wall-clock (host) save time
    path: str

    def modeled_cost(self, bandwidth: float, n_shards: int = 1) -> float:
        """Modeled checkpoint duration: per-shard bytes / bandwidth."""
        return self.bytes / max(1, n_shards) / bandwidth


class CheckpointManager:
    """Double-buffered full checkpoints + delta-encoded proactive ones."""

    def __init__(self, directory: str, *, keep: int = 2,
                 bandwidth: float = 2e9, block: int = 256) -> None:
        self.dir = directory
        self.keep = keep
        self.bandwidth = bandwidth
        self.block = block
        os.makedirs(directory, exist_ok=True)
        self._last_full_state: Any = None   # host copy backing deltas
        self._last_full_step: int = -1
        self._last_full_bytes: int = -1     # measured full payload size
        self._delta_ratios: list[float] = []  # measured delta/full ratios

    # -- paths ---------------------------------------------------------------

    def _full_path(self, step: int) -> str:
        return os.path.join(self.dir, f"full_{step:08d}.npz")

    def _delta_path(self, step: int) -> str:
        return os.path.join(self.dir, f"delta_{step:08d}.npz")

    def checkpoints(self) -> list[tuple[int, str]]:
        """Sorted [(step, kind)] of all durable checkpoints."""
        out = []
        for f in os.listdir(self.dir):
            m = re.match(r"(full|delta)_(\d+)\.npz$", f)
            if m:
                out.append((int(m.group(2)), m.group(1)))
        return sorted(out)

    def latest_step(self) -> int | None:
        cks = self.checkpoints()
        return cks[-1][0] if cks else None

    # -- full checkpoints ------------------------------------------------------

    def save(self, step: int, state: Any) -> SaveInfo:
        """Full checkpoint (paper cost C).  Atomic: tmp + rename."""
        t0 = time.perf_counter()
        host = jax.tree.map(np.asarray, jax.device_get(state))
        leaves = jax.tree.leaves(host)
        names = _leaf_names(host)
        payload = {f"leaf_{i}": _encode(l) for i, l in enumerate(leaves)}
        payload["__names__"] = np.asarray(json.dumps(names))
        path = self._full_path(step)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **payload)
        os.replace(tmp, path)  # durable before the old one is dropped
        secs = time.perf_counter() - t0
        self._last_full_state = host
        self._last_full_step = step
        self._gc()
        nbytes = os.path.getsize(path)
        self._last_full_bytes = nbytes
        return SaveInfo(step, "full", nbytes, secs, path)

    # -- proactive (delta) checkpoints ----------------------------------------

    def save_proactive(self, step: int, state: Any) -> SaveInfo:
        """Proactive checkpoint (paper cost C_p): int8 delta vs last full.

        Falls back to a full save if no full checkpoint exists yet.
        """
        if self._last_full_state is None:
            return self.save(step, state)
        t0 = time.perf_counter()
        host = jax.tree.map(np.asarray, jax.device_get(state))
        base_leaves = jax.tree.leaves(self._last_full_state)
        leaves = jax.tree.leaves(host)
        payload: dict[str, np.ndarray] = {}
        for i, (cur, base) in enumerate(zip(leaves, base_leaves)):
            if np.issubdtype(cur.dtype, np.floating) and cur.size >= self.block:
                q, scales = _delta.quantize_delta(
                    jnp.asarray(cur), jnp.asarray(base), block=self.block)
                payload[f"q_{i}"] = np.asarray(q)
                payload[f"s_{i}"] = np.asarray(scales)
            else:  # small / integer leaves stored raw
                payload[f"raw_{i}"] = _encode(cur)
        payload["__base__"] = np.asarray(self._last_full_step)
        path = self._delta_path(step)
        tmp = path + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **payload)
        os.replace(tmp, path)
        secs = time.perf_counter() - t0
        nbytes = os.path.getsize(path)
        if self._last_full_bytes > 0:
            self._delta_ratios.append(nbytes / self._last_full_bytes)
        return SaveInfo(step, "proactive", nbytes, secs, path)

    # -- restore ----------------------------------------------------------------

    def restore(self, like: Any, step: int | None = None) -> tuple[int, Any]:
        """Restore the latest (or a given) checkpoint into the structure of
        ``like`` (an abstract or concrete TrainState template)."""
        cks = self.checkpoints()
        if not cks:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        if step is None:
            step, kind = cks[-1]
        else:
            kind = dict(cks)[step]
        if kind == "full":
            return step, self._restore_full(like, step)
        return step, self._restore_delta(like, step)

    def _restore_full(self, like: Any, step: int) -> Any:
        with np.load(self._full_path(step), allow_pickle=False) as z:
            leaves = [z[f"leaf_{i}"]
                      for i in range(len(jax.tree.leaves(like)))]
        treedef = jax.tree.structure(like)
        flat_like = jax.tree.leaves(like)
        out = [_decode(l, t.dtype) for l, t in zip(leaves, flat_like)]
        return jax.tree.unflatten(treedef, out)

    def _restore_delta(self, like: Any, step: int) -> Any:
        with np.load(self._delta_path(step), allow_pickle=False) as z:
            base_step = int(z["__base__"])
            base = self._restore_full(like, base_step)
            flat_base, treedef = jax.tree.flatten(base)
            out = []
            for i, b in enumerate(flat_base):
                if f"q_{i}" in z:
                    cur = _delta.dequantize_delta(
                        jnp.asarray(z[f"q_{i}"]), jnp.asarray(z[f"s_{i}"]),
                        b, block=self.block)
                    out.append(cur.astype(b.dtype))
                else:
                    out.append(_decode(z[f"raw_{i}"], b.dtype))
        return jax.tree.unflatten(treedef, out)

    # -- cost model ---------------------------------------------------------------

    @property
    def measured_delta_ratio(self) -> float | None:
        """Mean measured proactive/full payload ratio, or None if this
        manager has not yet written a delta against a measured full."""
        if not self._delta_ratios:
            return None
        return sum(self._delta_ratios) / len(self._delta_ratios)

    def modeled_costs(self, state: Any, n_shards: int = 1,
                      delta_ratio: float | None = None) -> tuple[float, float]:
        """(C, C_p) in seconds from bytes/bandwidth.

        ``delta_ratio`` is the payload ratio of proactive vs full
        checkpoints (int8 + per-block scales over bf16/fp32 state).  When
        None (default) the ratio *measured from this manager's own saves*
        is used, so C_p tracks the actual ``ckpt_delta`` sparsity; before
        any delta has been written the ``DELTA_RATIO_PRIOR`` applies.
        """
        if delta_ratio is None:
            measured = self.measured_delta_ratio
            delta_ratio = DELTA_RATIO_PRIOR if measured is None else measured
        return modeled_costs_from_bytes(
            state_bytes(state), bandwidth=self.bandwidth, n_shards=n_shards,
            delta_ratio=delta_ratio)

    # -- gc -------------------------------------------------------------------

    def _gc(self) -> None:
        """Keep the last ``keep`` full checkpoints (+ deltas on them)."""
        fulls = [s for s, k in self.checkpoints() if k == "full"]
        for s in fulls[:-self.keep]:
            os.remove(self._full_path(s))
            for ds, dk in self.checkpoints():
                if dk == "delta":
                    with np.load(self._delta_path(ds)) as z:
                        if int(z["__base__"]) == s:
                            os.remove(self._delta_path(ds))
