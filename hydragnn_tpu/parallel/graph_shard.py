"""GSPMD node-sharded execution — the graph-sharding CORRECTNESS BASELINE.

This is the **fallback backend** behind the graph-sharding dispatcher
(``Training.graph_shard`` / HYDRAGNN_GRAPH_SHARD, resolved by
``graph/partition.py:GraphShardConfig``); the production backend is the
halo-exchange path (``graph/partition.py`` + ``parallel/mesh.py:
make_halo_train_step``).

What this backend actually does — and does NOT do: the node/edge arrays of
a ``GraphBatch`` are placed sharded along their leading axis and the
UNCHANGED model forward is ``jit``-ed against those shardings, letting
XLA's GSPMD partitioner insert the collectives.  Because the batch enters
the program with *unannotated* internal gathers (``x[senders]`` with
arbitrary cross-shard indices), GSPMD resolves every such gather by
**all-gathering the full node-feature array onto every device** — exactly
the repartitioning failure mode SNIPPETS.md's pjit exemplar warns
unannotated inputs hit.  Numerics are exact and no model code changes, but
peak per-device memory is the FULL ``[N, F]`` array (plus activations), so
this backend offers **zero memory headroom** over single-device execution.
``bench.py --giant`` measures both backends' largest node buffers;
docs/SCALING.md §6 records the numbers.  Use it to cross-check the halo
backend's numerics, not to fit bigger graphs.

Leading dims must divide the mesh size to shard; arrays that don't divide
(e.g. the [G]-sized graph arrays for odd graph counts) stay replicated —
correctness never depends on which arrays actually shard.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.parallel.mesh import DATA_AXIS


def batch_shardings(batch: GraphBatch, mesh: Mesh, axis: str = DATA_AXIS):
    """A pytree of NamedShardings matching ``batch``: every array whose
    leading dim divides the mesh size is split along it, others replicated.
    (None leaves — edge_attr/cell — are empty pytree nodes, never visited.)"""
    n_dev = mesh.devices.size

    def spec(arr):
        if arr.ndim >= 1 and arr.shape[0] % n_dev == 0:
            return NamedSharding(mesh, P(axis))
        return NamedSharding(mesh, P())

    return jax.tree.map(spec, batch)


def shard_batch(batch: GraphBatch, mesh: Mesh,
                axis: str = DATA_AXIS) -> GraphBatch:
    """Place ``batch`` with :func:`batch_shardings` (host -> sharded device
    arrays; each device holds 1/D of the node/edge rows AT REST — the
    full-array replication happens transiently inside the compiled
    program, see the module docstring)."""
    return jax.tree.map(jax.device_put, batch,
                        batch_shardings(batch, mesh, axis))


def make_sharded_forward(model, mesh: Mesh, train: bool = False):
    """jit of the unchanged ``model.apply`` with replicated params and
    node/edge-sharded batch; returns ``fn(variables, sharded_batch)``.

    Call :func:`shard_batch` on the input first — the batch's committed
    shardings (not a parameter here) are what jit respects, and GSPMD
    partitions every gather/segment-op around them (all-gathering the node
    array wherever it cannot)."""
    repl = NamedSharding(mesh, P())

    def fwd(variables, batch):
        return model.apply(variables, batch, train=train)

    return jax.jit(fwd, in_shardings=(repl, None), out_shardings=repl)


def make_gspmd_train_step(model, cfg, opt_spec, mesh: Mesh,
                          output_names: Optional[Sequence[str]] = None,
                          telemetry_metrics: bool = False,
                          nonfinite_guard: bool = False):
    """The baseline's TRAIN step: the plain local train step jit'd with
    replicated state and committed-sharded batch inputs — GSPMD inserts
    the (full-array) collectives.  Bit-comparable numerics for the halo
    backend to be checked against; no memory win (module docstring)."""
    from hydragnn_tpu.train.trainer import make_train_step

    repl = NamedSharding(mesh, P())
    step = make_train_step(
        model, cfg, opt_spec, output_names,
        telemetry_metrics=telemetry_metrics,
        nonfinite_guard=nonfinite_guard)

    def gspmd_train_step(state, g):
        return step(state, g)

    return jax.jit(gspmd_train_step, in_shardings=(repl, None),
                   out_shardings=repl, donate_argnums=0)


def make_gspmd_eval_step(model, cfg, mesh: Mesh):
    """Baseline eval step (replicated state, committed-sharded batch)."""
    from hydragnn_tpu.train.trainer import make_eval_step

    repl = NamedSharding(mesh, P())
    step = make_eval_step(model, cfg)

    def gspmd_eval_step(state, g):
        return step(state, g)

    return jax.jit(gspmd_eval_step,
                   in_shardings=(repl, None), out_shardings=repl)


class GspmdBatchLoader:
    """Wrap a GraphDataLoader so every yielded batch is placed with
    :func:`shard_batch` — the loader-side half of the gspmd baseline."""

    def __init__(self, loader, mesh: Mesh, axis: str = DATA_AXIS):
        self.loader = loader
        self.mesh = mesh
        self.axis = axis

    def set_epoch(self, epoch: int) -> None:
        self.loader.set_epoch(epoch)

    def __len__(self) -> int:
        return len(self.loader)

    def __iter__(self):
        for batch in self.loader:
            yield shard_batch(batch, self.mesh, self.axis)
