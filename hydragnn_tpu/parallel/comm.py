"""Host-side collectives for dataset construction.

The reference uses mpi4py (allreduce/allgather/bcast) for its data plane
(reference hydragnn/preprocess/utils.py:25-80, utils/adiosdataset.py).  Here
the data plane rides JAX's multi-host runtime: when
``jax.distributed.initialize`` has run, host-side numpy reductions go through
``jax.experimental.multihost_utils``; single-process runs short-circuit.
"""

from __future__ import annotations

from typing import List, Optional, Sequence

import numpy as np


def num_processes() -> int:
    import jax

    return jax.process_count()


def process_index() -> int:
    import jax

    return jax.process_index()


def host_allreduce(arr: np.ndarray, op: str = "sum") -> np.ndarray:
    """All-reduce a small numpy array across hosts (min/max/sum)."""
    import jax

    if jax.process_count() == 1:
        return np.asarray(arr)
    stacked = host_allgather(arr)
    if op == "sum":
        return np.sum(stacked, axis=0)
    if op == "min":
        return np.min(stacked, axis=0)
    if op == "max":
        return np.max(stacked, axis=0)
    raise ValueError(f"unknown op {op}")


def host_allgather(arr: np.ndarray) -> np.ndarray:
    """Gather a numpy array from every host; returns stacked [n_hosts, ...]."""
    import jax

    if jax.process_count() == 1:
        return np.asarray(arr)[None]
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(np.asarray(arr)))


def host_broadcast_scalar(value: float, root: int = 0) -> float:
    """Broadcast a host scalar from ``root`` (SLURM stop flags etc.)."""
    import jax

    if jax.process_count() == 1:
        return value
    return float(host_allgather(np.asarray([float(value)]))[root, 0])


def allgather_counts(local_count: int) -> List[int]:
    """Per-host counts (for rank-offset file naming, writer layouts)."""
    out = host_allgather(np.asarray([local_count], dtype=np.int64))
    return [int(c) for c in out.reshape(-1)]


def host_allgather_variable(arr: np.ndarray) -> np.ndarray:
    """Gather variable-length arrays across hosts by padding to the global
    max then stripping (parity: reference gather_tensor_ranks padding trick,
    hydragnn/train/train_validate_test.py:381-419)."""
    import jax

    arr = np.asarray(arr)
    if jax.process_count() == 1:
        return arr
    flat = arr.reshape(arr.shape[0], -1) if arr.ndim > 1 else arr[:, None]
    counts = allgather_counts(flat.shape[0])
    width = flat.shape[1]
    maxn = max(counts)
    padded = np.zeros((maxn, width), flat.dtype)
    padded[: flat.shape[0]] = flat
    stacked = host_allgather(padded)  # [n_hosts, maxn, width]
    parts = [stacked[r, : counts[r]] for r in range(len(counts))]
    out = np.concatenate(parts, axis=0)
    if arr.ndim == 1:
        return out[:, 0]
    return out.reshape((-1,) + arr.shape[1:])


class HostGroup:
    """Subgroup of hosts working on one branch of a multi-branch ensemble.

    The TPU-native analog of the reference's ``MPI.COMM_WORLD.Split`` per
    dataset corpus (reference examples/multidataset/train.py:205-247): hosts
    are partitioned by ``color``; collectives inside a group mask out other
    groups' contributions (gathers go through the global runtime with
    group-slot masking, since the JAX runtime has one global world).
    """

    def __init__(self, color: int):
        import jax

        self.color = int(color)
        colors = host_allgather(
            np.asarray([self.color], np.int64)).reshape(-1)
        self.members = [i for i, c in enumerate(colors) if c == self.color]
        self.size = len(self.members)
        self.rank = self.members.index(jax.process_index())

    def allreduce(self, arr: np.ndarray, op: str = "sum") -> np.ndarray:
        stacked = host_allgather(np.asarray(arr))
        if stacked.ndim == np.asarray(arr).ndim:
            return np.asarray(arr)
        group = stacked[self.members]
        if op == "sum":
            return group.sum(0)
        if op == "min":
            return group.min(0)
        if op == "max":
            return group.max(0)
        raise ValueError(op)

    def mean_scalar(self, value: float) -> float:
        return float(self.allreduce(np.asarray([value]), "sum")[0] / self.size)

    def mesh(self, axis: str = "data"):
        """1-axis data mesh over the member processes' devices.

        The TPU-native analog of training on a sub-communicator: each
        ensemble branch runs its OWN shard_map'd train step over its own
        group mesh, so gradients psum only within the branch (reference
        trains a DDP model per comm.Split subcommunicator,
        examples/multidataset/train.py:229-247).  Groups execute disjoint
        programs on disjoint devices — no cross-group collectives.
        """
        import jax
        from hydragnn_tpu.parallel.mesh import make_mesh

        members = set(self.members)
        devs = [d for d in jax.devices() if d.process_index in members]
        return make_mesh(devs, axis=axis)


def assign_ensemble_groups(weights: Sequence[float]) -> int:
    """Proportional host allocation over ensemble branches; returns this
    host's branch color (parity with the reference's proportional rank
    allocation, examples/multidataset/train.py:205-228)."""
    import jax

    n = jax.process_count()
    w = np.asarray(weights, np.float64)
    w = w / w.sum()
    if n < len(w):
        # fewer hosts than branches: round-robin coverage
        return int(jax.process_index() % len(w))
    alloc = np.maximum(1, np.floor(w * n).astype(int))
    while alloc.sum() > n:
        alloc[int(np.argmax(alloc))] -= 1
    while alloc.sum() < n:
        alloc[int(np.argmax(w - alloc / n))] += 1
    bounds = np.cumsum(alloc)
    return int(np.searchsorted(bounds, jax.process_index(), side="right"))
