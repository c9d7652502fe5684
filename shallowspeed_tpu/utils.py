"""Distributed-correctness utilities.

Capability parity with `/root/reference/shallowspeed/utils.py:8-31` (rank-0
print, model hashing, cross-replica sync assertion), re-targeted at
single-controller JAX: "rank 0" becomes `jax.process_index() == 0`, and the
sync check hashes the per-device shards of a sharded/replicated params pytree
instead of MPI-gathering.
"""

from __future__ import annotations

import hashlib
from typing import Any

import jax
import numpy as np


def rprint(*args, **kwargs):
    """Print once per job (reference `utils.py:8-10` prints on MPI rank 0)."""
    if jax.process_index() == 0:
        print(*args, **kwargs)


def get_model_hash(params: Any) -> str:
    """SHA-1 over the concatenated per-leaf SHA-1s (reference `utils.py:13-24`)."""
    leaves = jax.tree_util.tree_leaves(params)
    combo = hashlib.sha1()
    for leaf in leaves:
        arr = np.asarray(jax.device_get(leaf))
        combo.update(hashlib.sha1(np.ascontiguousarray(arr).tobytes()).hexdigest()
                     .encode())
    return combo.hexdigest()


def assert_replicas_in_sync(params: Any) -> None:
    """Assert every device shard of a replicated params pytree is bit-identical.

    The reference gathers per-rank model hashes to root and raises on mismatch
    after training (`utils.py:27-31`, `train.py:154-155`). Under
    single-controller JAX, DP replicas are the per-device copies of arrays
    replicated over the `dp` mesh axis; we hash each addressable shard.
    """
    for leaf in jax.tree_util.tree_leaves(params):
        if not isinstance(leaf, jax.Array):
            continue
        # Group shards by the logical index they hold: replicas of the same
        # slice (e.g. dp-replicated copies of a pp shard) must be identical.
        by_slice: dict[tuple, set[str]] = {}
        for shard in leaf.addressable_shards:
            key = tuple((s.start, s.stop, s.step) for s in shard.index)
            arr = np.asarray(shard.data)
            h = hashlib.sha1(np.ascontiguousarray(arr).tobytes()).hexdigest()
            by_slice.setdefault(key, set()).add(h)
        for key, hashes in by_slice.items():
            if len(hashes) > 1:
                raise AssertionError(
                    f"DP replicas out of sync for leaf {leaf.shape} slice "
                    f"{key}: {sorted(hashes)}")


def pvary_over(tree: Any, axes: tuple[str, ...]) -> Any:
    """Cast a pytree to 'varying' over the given shard_map mesh axes (VMA).

    Inside `shard_map`, axis-invariant constants (e.g. a zeros scan-carry
    init) and axis-varying data (e.g. outputs of `ppermute`) have different
    types; this casts the former so carries typecheck. Axes a leaf already
    varies over are left alone (pcast rejects those).
    """
    def cast(leaf):
        missing = tuple(ax for ax in axes if ax not in jax.typeof(leaf).vma)
        return jax.lax.pcast(leaf, missing, to="varying") if missing else leaf

    return jax.tree_util.tree_map(cast, tree)
