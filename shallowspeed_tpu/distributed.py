"""Multi-host distributed runtime: process init, ICI x DCN meshes, and
host-local data placement.

The reference scales across hosts with `mpirun` + MPI communicator splits
(`/root/reference/train.py:87-94`, noting `Split_type`/`TYPE_SOCKET` for
"physically distributed" runs, `train.py:90-91`). The TPU-native
counterpart is multi-controller JAX: one Python process per host, all
connected through the JAX distributed service; collectives ride ICI inside
a pod slice and DCN between slices, compiled into the XLA program — no
MPI/NCCL dependency.

Everything in this module degrades to a no-op / plain-JAX behavior in a
single-process run, so the same driver script works from one chip to a
multi-pod fleet:

- `initialize()`: `jax.distributed.initialize` with env-var autodetection,
  idempotent, no-op when single-process.
- `hybrid_mesh(...)`: an ICI x DCN-aware mesh. The slowest-varying
  (leftmost) axes land on DCN, per the scaling-book recipe: data
  parallelism (gradient all-reduce, one collective per step) tolerates
  DCN latency; model axes (tp/sp collectives on every layer) must stay
  on ICI inside a slice.
- `place_global(...)`: build a globally-sharded array from each process's
  host-local batch shard — the multi-host replacement for
  `jax.device_put(np_array, sharding)`, which only works when every
  process holds the full global array.
- `process_zero()` / `barrier()`: control-plane helpers (the reference's
  rank-0 guard and sync points).
"""

from __future__ import annotations

import functools
import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def initialize(coordinator_address: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None) -> bool:
    """Connect this process to the JAX distributed service.

    Call once per process, before first backend use. Arguments default to
    the standard env vars (`JAX_COORDINATOR_ADDRESS`, `JAX_NUM_PROCESSES`,
    `JAX_PROCESS_ID`). Strictly opt-in: without an explicit coordinator
    address (argument or env var) this is a no-op, even on hardware whose
    metadata advertises a pod — single-host TPU images often do, and an
    unwanted init attempt after backend startup is a hard error. Returns True if a multi-process
    runtime was set up, False for the single-process no-op or when already
    initialized (idempotent).
    """
    coordinator_address = (coordinator_address
                           or os.environ.get("JAX_COORDINATOR_ADDRESS"))
    if coordinator_address is None:
        return False  # single-process run
    if jax.distributed.is_initialized():
        return False  # idempotent
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=(num_processes
                       if num_processes is not None
                       else _env_int("JAX_NUM_PROCESSES")),
        process_id=(process_id if process_id is not None
                    else _env_int("JAX_PROCESS_ID")))
    return True


def _env_int(name: str) -> int | None:
    v = os.environ.get(name)
    return int(v) if v is not None else None


def process_zero() -> bool:
    """The reference's rank-0 guard (`utils.py:8-10`), multi-controller."""
    return jax.process_index() == 0


def barrier(tag: str = "barrier") -> None:
    """Block until every process reaches this point (no-op single-process).
    The control-plane sync the reference gets implicitly from MPI
    collectives (`utils.py:27-31`)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(tag)


def all_ok(flag: bool) -> bool:
    """Collective AND of a per-process success bit; doubles as a barrier.

    Use wherever one process can fail while its peers would otherwise
    proceed trusting shared state (e.g. an async checkpoint write that
    only process 0 performs): every process learns the fleet-wide
    verdict at the same point, so failures raise TOGETHER instead of
    wedging the gang in the next collective. Single-process: returns
    `flag` unchanged."""
    if jax.process_count() == 1:
        return bool(flag)
    from jax.experimental import multihost_utils

    bits = multihost_utils.process_allgather(np.asarray(bool(flag)))
    return bool(np.all(bits))


def hybrid_mesh(axis_names: tuple[str, ...], axis_sizes: tuple[int, ...],
                *, dcn_axes: int = 1, devices=None) -> Mesh:
    """A mesh whose leftmost `dcn_axes` axes span slices over DCN and whose
    remaining axes stay inside a slice on ICI.

    Single-slice / single-host (or CPU-simulated) runs fall back to a plain
    row-major reshape — same axis names, same program, so drivers don't
    branch. Axis ORDER is the contract: put dp (and fsdp) leftmost, model
    axes (sp/tp/ep, pp) rightmost, because the leftmost axes get the
    slow links (one gradient collective per step) and the rightmost get
    ICI (collectives on every layer).
    """
    assert len(axis_names) == len(axis_sizes)
    if devices is None:
        devices = jax.devices()
    n = int(np.prod(axis_sizes))
    assert n <= len(devices), (
        f"mesh {dict(zip(axis_names, axis_sizes))} needs {n} devices, "
        f"have {len(devices)}")
    by_slice: dict[int, list] = {}
    for d in devices:
        by_slice.setdefault(getattr(d, "slice_index", 0), []).append(d)
    if len(by_slice) > 1:
        from jax.experimental import mesh_utils

        dcn = int(np.prod(axis_sizes[:dcn_axes]))
        per_slice = int(np.prod(axis_sizes[dcn_axes:]))
        if dcn != len(by_slice):
            raise ValueError(
                f"the leftmost {dcn_axes} (DCN) axes have product {dcn} "
                f"but the fleet has {len(by_slice)} slices; size the DCN "
                f"axes to the slice count (or pass a `devices` subset)")
        short = {s: len(v) for s, v in by_slice.items() if len(v) < per_slice}
        if short:
            raise ValueError(
                f"ICI axes need {per_slice} devices per slice; slices "
                f"{sorted(short)} have only {short}")
        picked = [d for s in sorted(by_slice)
                  for d in by_slice[s][:per_slice]]
        grid = mesh_utils.create_hybrid_device_mesh(
            mesh_shape=axis_sizes[dcn_axes:],
            dcn_mesh_shape=axis_sizes[:dcn_axes] + (1,) * (
                len(axis_sizes) - dcn_axes),
            devices=picked)
        return Mesh(grid.reshape(axis_sizes), axis_names)
    grid = np.array(devices[:n]).reshape(axis_sizes)
    return Mesh(grid, axis_names)


def place_global(arr: np.ndarray, sharding: NamedSharding,
                 local: bool = True) -> jax.Array:
    """Assemble a globally-sharded jax.Array across processes.

    Single-process: plain `device_put` (arr is the global array).
    Multi-process, `local=True` (default): `arr` is this host's shard of
    the global batch — e.g. with the global batch sharded over 'dp' and
    P processes, each process passes its B/P rows — and the pieces are
    stitched into one global array without any host ever holding the
    whole thing. This is how the reference's per-rank
    `Dataset.load(DP_rank, DP_size)` strided shards (`dataset.py:54-58`)
    map to single-controller-per-host JAX.

    Multi-process, `local=False`: every process holds the SAME full
    global array (deterministically built batches); each device pulls
    its slice via `make_array_from_callback`. Callers that replicate
    batch construction (the pipeline engine's microbatch splitter) MUST
    use this form — `make_array_from_process_local_data` would silently
    misread a full-global array as the process-local block whenever a
    sharded dimension spans processes.
    """
    if isinstance(arr, jax.Array) or jax.process_count() == 1:
        # already placed (no-op/reshard) or single-process global array
        return jax.device_put(arr, sharding)
    if not local:
        arr = np.asarray(arr)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx])
    return jax.make_array_from_process_local_data(sharding, arr)


def local_rows(arr: np.ndarray) -> np.ndarray:
    """This process's row-block of a globally-identical batch.

    Drivers build batches deterministically (seeded per step) so every
    process materializes the same global array; each keeps only its
    contiguous `B/P` rows to feed `place_global`. No-op single-process.
    Row-block (not strided) so the concatenation order
    `make_array_from_process_local_data` assumes matches row order.
    """
    p = jax.process_count()
    if p == 1:
        return arr
    assert arr.shape[0] % p == 0, (
        f"global batch of {arr.shape[0]} rows must divide over {p} "
        f"processes")
    rows = arr.shape[0] // p
    i = jax.process_index()
    return arr[i * rows:(i + 1) * rows]


def fetch_global(tree):
    """`jax.device_get` that also works on MULTI-CONTROLLER globally
    sharded pytrees (round 4 — the checkpoint path's fetch).

    Single-process: plain device_get. Multi-process: a leaf sharded
    over a mesh axis that spans processes is not fully addressable, so
    device_get would raise; replicate every jax.Array leaf first (jit
    identity with replicated out_shardings — XLA inserts the
    all-gathers, riding ICI/DCN) and read the now-local full copy.
    Collective: EVERY process must call this together (same order), the
    same way they issue training steps."""
    if jax.process_count() == 1:
        return jax.device_get(tree)

    def fetch(leaf):
        if not isinstance(leaf, jax.Array):
            return np.asarray(leaf)
        sh = getattr(leaf, "sharding", None)
        if getattr(leaf, "is_fully_addressable", True):
            return np.asarray(jax.device_get(leaf))
        rep = _replicator(NamedSharding(sh.mesh, PartitionSpec()))(leaf)
        return np.asarray(jax.device_get(rep))

    return jax.tree_util.tree_map(fetch, tree)


@functools.lru_cache(maxsize=64)
def _replicator(sharding: NamedSharding):
    """Cached jitted identity-with-replication: jit caches on function
    identity, so a fresh lambda per leaf would recompile the replicate
    program on every checkpoint save — one program per target sharding
    (per mesh) serves every leaf instead."""
    return jax.jit(lambda x: x, out_shardings=sharding)
