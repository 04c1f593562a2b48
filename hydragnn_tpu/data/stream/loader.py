"""StreamingGraphLoader: bounded-memory epoch iteration over a gpack store.

Duck-types the in-memory ``GraphDataLoader`` protocol (set_epoch, __len__,
__iter__, ``_index_plan``/``_collate_index_item`` for the process-pool
collate, pad_specs/bucket_group/padding_efficiency for the pipeline
auto-tuner) while holding only index arrays and per-sample size arrays —
never the decoded dataset.  Decoded samples live in a refcounted window of
at most ~W entries inside ``__iter__``; each is evicted the moment its
last planned use is collated.

It deliberately does NOT define ``_batch_plan``: PrefetchLoader's
thread-pool path materializes that plan (every decoded sample of the
epoch at once), which is exactly the unbounded residency this subsystem
removes.  Absent the method, PrefetchLoader runs its sequential
background-iterator branch — bounded queue, bounded memory — and
ProcessPrefetchLoader uses ``_index_plan``, whose items are index arrays.

Mid-epoch resume: :meth:`StreamingGraphLoader.fast_forward` arms a
skip-first-N that drops the first N *planned* batches of the next
iteration (spec grouping is computed over the FULL epoch first, so batch
N+1 onward is bit-identical to an uninterrupted epoch — the property
``tools/crashtest.py --stream`` proves).  :func:`try_fast_forward` walks
a wrapped loader chain and converts wrapper-level units (device-stacked
steps) into base-loader batches.
"""

from __future__ import annotations

import math
import os
from collections import Counter
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from hydragnn_tpu.data.dataloader import (
    bucket_pad_specs_from_sizes,
    pad_spec_from_sizes,
)
from hydragnn_tpu.data.stream.plan import StreamPlan
from hydragnn_tpu.graph.batch import (
    GraphBatch,
    GraphSample,
    HeadSpec,
    PadSpec,
    collate,
)
from hydragnn_tpu.telemetry import pipeline as tele_pipe
from hydragnn_tpu.utils import tracer


def _sample_nbytes(s: GraphSample) -> int:
    total = 0
    for k in ("x", "pos", "edge_index", "edge_attr", "graph_y", "node_y",
              "cell"):
        v = getattr(s, k, None)
        if v is not None:
            total += int(v.nbytes)
    for v in (s.extras or {}).values():
        total += int(np.asarray(v).nbytes)
    return total


class StreamingGraphLoader:
    """Padded-batch iteration over a gpack store with O(window) residency.

    ``indices`` are positions into ``store`` (the split's rows); ordering
    and the per-host share come from :class:`StreamPlan`, which in
    ``global`` mode reproduces ``GraphDataLoader._local_indices``
    bit-exactly — streamed batches equal in-memory batches on the same
    seed, for ANY window size (the window bounds residency, not order).
    """

    is_streaming = True

    def __init__(
        self,
        store,
        indices: Sequence[int],
        head_specs: Sequence[HeadSpec],
        batch_size: int,
        window: int = 1024,
        shuffle: bool = False,
        seed: int = 0,
        order: str = "global",
        block: int = 2048,
        graph_feature_slices: Optional[Sequence[Tuple[int, int]]] = None,
        node_feature_slices: Optional[Sequence[Tuple[int, int]]] = None,
        rank: int = 0,
        world_size: int = 1,
        drop_last: bool = False,
        post_collate=None,
        pad_specs: Optional[Sequence[PadSpec]] = None,
        bucket_group: int = 1,
        tail_dir: Optional[str] = None,
    ):
        self.store = store
        self.indices = np.asarray(indices, np.int64)
        self.head_specs = list(head_specs)
        self.batch_size = int(batch_size)
        self.window = max(1, int(window))
        self.shuffle = shuffle
        self.seed = seed
        self.order = order
        self.block = int(block)
        self.rank = rank
        self.world_size = world_size
        self.drop_last = drop_last
        self.epoch = 0
        self.graph_feature_slices = graph_feature_slices
        self.node_feature_slices = node_feature_slices
        self.post_collate = post_collate
        self.tail_dir = tail_dir or None
        self._refresh_sizes()
        if pad_specs is not None:
            self.pad_specs = sorted(pad_specs, key=lambda p: p.num_nodes)
            pad_spec = self.pad_specs[-1]  # worst-case bucket
        else:
            pad_spec = pad_spec_from_sizes(
                self._nodes, self._edges, self.batch_size)
            self.pad_specs = [pad_spec]
        self.pad_spec = pad_spec
        self.bucket_group = max(1, int(bucket_group))
        # padding-waste accounting, reset per epoch (protocol parity)
        self.real_nodes = 0
        self.padded_nodes = 0
        # armed by fast_forward(); consumed by the next plan materialization
        self._skip = 0
        # largest decoded-resident count seen by the last __iter__ — the
        # bounded-memory invariant tests/test_stream.py asserts on
        self.last_resident_peak = 0
        # tail growth noted by maybe_refresh (trainer emits the health event)
        self.tail_grew: Optional[Tuple[int, int]] = None

    # -- sizes / plan ------------------------------------------------------
    def _refresh_sizes(self) -> None:
        nodes, edges = self.store.sizes()
        self._nodes = nodes[self.indices]
        self._edges = edges[self.indices]

    def _plan_obj(self) -> StreamPlan:
        return StreamPlan(
            n_total=len(self.indices),
            seed=self.seed,
            rank=self.rank,
            world_size=self.world_size,
            shuffle=self.shuffle,
            mode=self.order,
            block=self.block,
        )

    def plan(self) -> StreamPlan:
        """This loader's StreamPlan — public accessor for callers that
        need the plan's identity rather than its order (the trainer
        records ``plan().fingerprint()`` in the resume bundle's ``world``
        block so an elastic resume can validate it replays the same
        global order; resilience/elastic.py)."""
        return self._plan_obj()

    def set_epoch(self, epoch: int) -> None:
        """Reseed the shuffle (parity: DistributedSampler.set_epoch); in
        tail mode also pick up newly sealed ingest segments."""
        self.epoch = epoch
        if self.tail_dir:
            self.maybe_refresh()

    def maybe_refresh(self) -> bool:
        """Tail mode: re-read the ingest manifest; when new sealed segments
        appeared, swap in a fresh store over the grown segment list (the
        old store object stays alive for any forked collate workers)."""
        if not self.tail_dir:
            return False
        from hydragnn_tpu.data.stream.ingest import open_tail_store

        new_store = open_tail_store(self.tail_dir)
        if new_store is None or len(new_store) <= len(self.store):
            if new_store is not None and new_store is not self.store:
                new_store.close()
            return False
        old_n = len(self.store)
        self.store = new_store
        self.indices = np.arange(len(new_store), dtype=np.int64)
        self._refresh_sizes()
        self.tail_grew = (old_n, len(new_store))
        return True

    def padding_efficiency(self) -> float:
        """real node slots / padded node slots over batches yielded so far."""
        return self.real_nodes / max(self.padded_nodes, 1)

    def _local_indices(self) -> np.ndarray:
        return self._plan_obj().epoch_order(self.epoch)

    def __len__(self) -> int:
        n = self._plan_obj().host_share()
        if self.drop_last:
            return n // self.batch_size
        return int(math.ceil(n / self.batch_size))

    # -- fast-forward ------------------------------------------------------
    def fast_forward(self, n_batches: int) -> None:
        """Arm a skip of the first ``n_batches`` planned batches for the
        NEXT plan materialization (one epoch), then disarm.  The epoch plan
        (order, bucket-spec grouping, padding counters) is computed in full
        first, so the surviving batches are bit-identical to the same
        positions of an uninterrupted epoch."""
        self._skip = max(0, int(n_batches))

    # -- planning ----------------------------------------------------------
    def _pick_spec(self, idx_groups: Sequence[np.ndarray]) -> PadSpec:
        """Smallest bucket that fits every batch in the group — sized from
        the header size arrays, no decode."""
        need_nodes = max(int(self._nodes[ix].sum()) for ix in idx_groups)
        need_edges = max(int(self._edges[ix].sum()) for ix in idx_groups)
        for spec in self.pad_specs:
            if spec.num_nodes - 1 >= need_nodes \
                    and spec.num_edges >= need_edges:
                return spec
        return self.pad_specs[-1]

    def _index_plan(self) -> List[Tuple[np.ndarray, PadSpec]]:
        """The epoch's (sample-index array, pad_spec) per batch — index
        arrays are positions into ``self.indices`` — computed over the
        FULL epoch, then truncated by an armed fast-forward.  Also the
        process-pool collate protocol (prefetch.py)."""
        order = self._local_indices()
        n = len(order)
        nb = n // self.batch_size if self.drop_last \
            else int(math.ceil(n / self.batch_size))
        skip, self._skip = self._skip, 0
        self.real_nodes = 0
        self.padded_nodes = 0
        plan: List[Tuple[np.ndarray, PadSpec]] = []
        for g0 in range(0, nb, self.bucket_group):
            idxs = [order[b * self.batch_size:(b + 1) * self.batch_size]
                    for b in range(g0, min(g0 + self.bucket_group, nb))]
            if len(self.pad_specs) == 1:
                spec = self.pad_spec
            else:
                spec = self._pick_spec(idxs)
            for ix in idxs:
                plan.append((np.asarray(ix), spec))
        if skip:
            plan = plan[skip:]
        for ix, spec in plan:
            self.real_nodes += int(self._nodes[ix].sum())
            self.padded_nodes += spec.num_nodes
        return plan

    # -- decode / collate --------------------------------------------------
    def _decode(self, local_pos: int) -> GraphSample:
        s = self.store.get(int(local_pos_to_store(self, local_pos)))
        if tele_pipe.enabled():
            tele_pipe.add("stream_read_samples", 1)
            tele_pipe.add("stream_read_bytes", _sample_nbytes(s))
        return s

    def _collate_index_item(
        self, item: Tuple[np.ndarray, PadSpec]
    ) -> GraphBatch:
        idx, spec = item
        return self._collate_plan_item(
            ([self._decode(i) for i in idx], spec))

    def _collate_plan_item(
        self, item: Tuple[List[GraphSample], PadSpec]
    ) -> GraphBatch:
        """Pure (thread-safe) collation of one planned batch."""
        batch, spec = item
        with tracer.timer("data.collate"):
            out = collate(
                batch,
                spec,
                self.head_specs,
                self.graph_feature_slices,
                self.node_feature_slices,
            )
            if self.post_collate is not None:
                out = self.post_collate(out)
        if tele_pipe.enabled():
            tele_pipe.add("collate_bytes", tele_pipe.batch_nbytes(out))
            tele_pipe.add("collate_batches", 1)
        return out

    def __iter__(self) -> Iterator[GraphBatch]:
        plan = self._index_plan()
        W = self.window
        cache: Dict[int, GraphSample] = {}
        # per-position remaining-use refcounts (wrap-pad duplicates a
        # position across batches; decode once, keep until its last use)
        left: Counter = Counter()
        flat: List[int] = []
        for ix, _spec in plan:
            for i in ix:
                left[int(i)] += 1
                flat.append(int(i))
        cursor = 0
        peak = 0
        for ix, spec in plan:
            need = [int(i) for i in ix]
            # the current batch is ALWAYS decoded, even when W < batch
            # size (residency then transiently exceeds W by the batch)
            for i in need:
                if i not in cache:
                    cache[i] = self._decode(i)
            # decode ahead in planned-use order while the window has room
            while cursor < len(flat) and len(cache) < W:
                j = flat[cursor]
                if j not in cache:
                    cache[j] = self._decode(j)
                cursor += 1
            peak = max(peak, len(cache))
            if tele_pipe.enabled():
                tele_pipe.add("stream_window_fill_sum",
                              100.0 * len(cache) / W)
                tele_pipe.add("stream_window_fill_gets", 1)
            yield self._collate_plan_item(([cache[i] for i in need], spec))
            for i in need:
                left[i] -= 1
                if left[i] <= 0:
                    cache.pop(i, None)
        self.last_resident_peak = peak

    def close(self) -> None:
        self.store.close()


def local_pos_to_store(loader: StreamingGraphLoader, local_pos: int) -> int:
    """Map a plan position (into ``loader.indices``) to a store position."""
    return int(loader.indices[int(local_pos)])


# ---------------------------------------------------------------------------
# wrapped-chain helpers
# ---------------------------------------------------------------------------


def find_stream_loader(loader) -> Optional[StreamingGraphLoader]:
    """Walk a wrapper chain (``.loader`` attributes) to the streaming base
    loader, or None if the chain bottoms out elsewhere."""
    obj = loader
    while obj is not None:
        if getattr(obj, "is_streaming", False):
            return obj
        obj = getattr(obj, "loader", None)
    return None


def try_fast_forward(loader, n_units: int) -> bool:
    """Arm skip-first-N on the streaming base of a wrapped loader chain.

    ``n_units`` is in the FINAL wrapped loader's dispatch units (what the
    resume bundle's ``items_consumed`` counts); each DeviceStackLoader in
    the chain multiplies the base-batch count by its device fan-in.
    Returns False (caller falls back to iterate-and-discard) when the
    chain has no streaming base or a wrapper that buffers batches.
    """
    mult = 1
    obj = loader
    while obj is not None:
        if getattr(obj, "is_streaming", False):
            obj.fast_forward(int(n_units) * mult)
            return True
        n_dev = getattr(obj, "n_devices", None)
        if n_dev:
            mult *= int(n_dev)
        obj = getattr(obj, "loader", None)
    return False


# ---------------------------------------------------------------------------
# store-level statistics (DatasetStats without materializing samples)
# ---------------------------------------------------------------------------


def stats_from_store(store, need_deg: bool = False):
    """``DatasetStats.from_samples`` computed one sample at a time over a
    gpack store: sizes come from the part headers; only the PNA degree
    histogram decodes anything (edge_index, one sample at a time)."""
    from hydragnn_tpu.config.config import DatasetStats

    nodes, edges = store.sizes()
    if len(nodes) == 0:
        raise ValueError("cannot compute dataset stats over an empty store")
    pna_deg = None
    if need_deg:
        max_deg = 0
        for i in range(len(nodes)):
            if edges[i]:
                ei = store.sample_view(i, "edge_index")
                d = np.bincount(ei[1], minlength=int(nodes[i]))
                max_deg = max(max_deg, int(d.max()))
        hist = np.zeros(max_deg + 1, dtype=np.int64)
        for i in range(len(nodes)):
            if edges[i]:
                ei = store.sample_view(i, "edge_index")
                d = np.bincount(ei[1], minlength=int(nodes[i]))
            else:
                d = np.zeros(int(nodes[i]), dtype=np.int64)
            hist += np.bincount(d, minlength=max_deg + 1)
        pna_deg = hist.tolist()
    return DatasetStats(
        num_nodes_sample=int(nodes[0]),
        graph_size_variable=len(np.unique(nodes)) > 1,
        pna_deg=pna_deg,
        max_nodes=int(nodes.max()),
        max_edges=int(edges.max()),
    )


def max_triplets_from_store(store) -> int:
    """Worst-case DimeNet triplet count per sample, decoding edge_index one
    sample at a time (streaming analog of the load_data scan)."""
    from hydragnn_tpu.models.dimenet import count_triplets

    nodes, edges = store.sizes()
    max_per = 1
    for i in range(len(nodes)):
        if edges[i]:
            ei = np.asarray(store.sample_view(i, "edge_index"))
            max_per = max(max_per, count_triplets(ei, int(nodes[i])))
    return max_per


# ---------------------------------------------------------------------------
# three-way split + loader construction (create_dataloaders analog)
# ---------------------------------------------------------------------------


def split_stream_indices(
    n: int, perc_train: float
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Contiguous train/val/test position ranges with the same arithmetic
    as ``splitting.split_dataset`` (non-stratified path)."""
    n_train = int(perc_train * n)
    n_val = int(((1 - perc_train) / 2) * n)
    return (
        np.arange(0, n_train, dtype=np.int64),
        np.arange(n_train, n_train + n_val, dtype=np.int64),
        np.arange(n_train + n_val, n, dtype=np.int64),
    )


def create_stream_dataloaders(
    store,
    splits: Tuple[np.ndarray, np.ndarray, np.ndarray],
    batch_size: int,
    head_specs: Sequence[HeadSpec],
    stream_cfg,
    graph_feature_slices=None,
    node_feature_slices=None,
    rank: int = 0,
    world_size: int = 1,
    seed: int = 0,
    post_collate=None,
    n_buckets: Optional[int] = None,
    bucket_group: Optional[int] = None,
):
    """Three StreamingGraphLoaders sharing one PadSpec set — the streaming
    mirror of ``dataloader.create_dataloaders`` (same bucket-count env
    logic, same prefetch-wrapper env knobs), sized entirely from header
    size arrays."""
    train_ix, val_ix, test_ix = splits
    nodes, edges = store.sizes()
    all_ix = np.concatenate([train_ix, val_ix, test_ix])
    if n_buckets is None:
        n_buckets = int(os.getenv("HYDRAGNN_NUM_BUCKETS", "0") or 0)
        if n_buckets < 1:
            from hydragnn_tpu.utils.env import env_flag

            n_buckets = 4 if env_flag("HYDRAGNN_USE_VARIABLE_GRAPH_SIZE") \
                else 3
    if world_size > 1:
        n_buckets = 1
    if n_buckets > 1:
        pads = bucket_pad_specs_from_sizes(
            nodes[all_ix], edges[all_ix], batch_size, n_buckets)
        if bucket_group is None:
            import jax

            bucket_group = len(jax.local_devices())
    else:
        pads = [pad_spec_from_sizes(nodes[all_ix], edges[all_ix],
                                    batch_size)]
        bucket_group = 1
    mk = lambda split, shuffle, tail: StreamingGraphLoader(
        store,
        split,
        head_specs,
        batch_size,
        window=stream_cfg.window,
        shuffle=shuffle,
        seed=seed,
        order=stream_cfg.order,
        block=stream_cfg.block,
        graph_feature_slices=graph_feature_slices,
        node_feature_slices=node_feature_slices,
        rank=rank,
        world_size=world_size,
        post_collate=post_collate,
        pad_specs=pads,
        bucket_group=bucket_group,
        tail_dir=tail,
    )
    # tail mode: only the TRAIN loader follows the growing manifest (val
    # and test keep a stable snapshot so eval numbers stay comparable)
    tail = stream_cfg.tail or None
    loaders = (mk(train_ix, True, tail), mk(val_ix, False, None),
               mk(test_ix, False, None))
    n_procs = int(os.getenv("HYDRAGNN_COLLATE_PROCS", "0"))
    if n_procs > 0:
        from hydragnn_tpu.data.prefetch import ProcessPrefetchLoader

        return tuple(
            ProcessPrefetchLoader(l, num_workers=n_procs) for l in loaders)
    n_workers = int(os.getenv("HYDRAGNN_NUM_WORKERS", "0"))
    if n_workers > 0:
        from hydragnn_tpu.data.prefetch import PrefetchLoader

        loaders = tuple(
            PrefetchLoader(l, num_workers=n_workers) for l in loaders)
    return loaders
