"""Prefetching loader: overlap host-side collation with device compute.

The TPU-native analog of the reference's HydraDataLoader (reference
hydragnn/preprocess/load_data.py:94-204): a ThreadPoolExecutor-backed custom
loader built to keep accelerators fed (theirs pins CPU affinity per worker to
dodge torch DataLoader hangs on Summit/Perlmutter).  Here the loader runs
collation in a background thread pool and keeps a bounded queue of ready
batches ahead of the training step; optional CPU affinity pinning matches
the reference's HYDRAGNN_AFFINITY behavior.
"""

from __future__ import annotations

import os

import numpy as np
import queue
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, Optional

# input-pipeline telemetry counters (no-ops unless a MetricsLogger enabled
# them — see hydragnn_tpu/telemetry/pipeline.py)
from hydragnn_tpu.telemetry import pipeline as tele_pipe
from hydragnn_tpu.utils import tracer


def drain_bounded_queue(q, sentinel, stop, on_item=None) -> None:
    """Leak-safe shutdown of a bounded producer/consumer queue (the ONE
    idiom shared by the prefetch loaders and the serving micro-batcher):
    signal ``stop``, then swallow in-flight items on a daemon thread until
    ``sentinel`` arrives, so a producer blocked on ``q.put`` can finish
    and exit instead of leaking its thread (and whatever its items pin).

    ``on_item`` releases per-item resources the abandonment would
    otherwise leak (e.g. failing a pending request future so its waiter
    unblocks).  Error-propagating producers may wrap the sentinel as
    ``(sentinel, err)``; both forms terminate the drain.
    """
    stop.set()

    def run():
        while True:
            item = q.get()
            if item is sentinel or (
                    isinstance(item, tuple) and len(item) == 2
                    and item[0] is sentinel):
                break
            if on_item is not None:
                try:
                    on_item(item)
                except Exception:  # graftlint: disable=ROB001 (leak-guard drain; release is best-effort)
                    pass

    threading.Thread(target=run, daemon=True).start()


def _make_stage(sharding=None):
    """Device-staging function shared by DevicePrefetcher and
    ResidentDeviceLoader: a jitted identity whose argument-ingest transfer
    path coalesces the batch pytree's leaves into one transfer instead of
    one device_put per leaf.  Batches already staged with
    the target placement pass through untouched, so composing the two
    wrappers doesn't double-dispatch.

    With ``sharding=None`` any batch whose leaves are already ``jax.Array``
    passes through regardless of placement: None-sharding staging is for
    single-device pipelines (how the trainer uses it), where the default
    device is the only possible placement."""
    import jax

    def stage_batch(t):
        return t

    if sharding is not None:
        ident = jax.jit(stage_batch, out_shardings=sharding)
    else:
        ident = jax.jit(stage_batch)

    def stage(batch):
        leaves = jax.tree_util.tree_leaves(batch)
        if leaves and all(isinstance(l, jax.Array) for l in leaves):
            if sharding is None or all(
                    l.sharding == sharding for l in leaves):
                return batch
        if tele_pipe.enabled():
            # host->device transfer accounting: only batches that actually
            # dispatch a transfer count (already-staged passthroughs above
            # moved nothing)
            tele_pipe.add("h2d_bytes", tele_pipe.batch_nbytes(batch))
            tele_pipe.add("h2d_batches", 1)
        # the call ingests the arguments: the host->device copy is
        # enqueued (and, from numpy, made) before it returns
        with tracer.timer("data.h2d"):
            return ident(batch)

    return stage


class DevicePrefetcher:
    """Background ``jax.device_put`` with bounded lookahead.

    Collation prefetch (PrefetchLoader) still hands the step numpy batches,
    so every step pays a synchronous host->device transfer that
    serializes with compute.  This wrapper starts the
    async transfer for the NEXT batch(es) while the current step runs:
    ``jax.device_put`` returns immediately and the copy proceeds in the
    background, so the step finds its input already on device.

    ``sharding`` places stacked [D, ...] batches directly with a mesh
    sharding (single-process multi-device path); None targets the default
    device.  Not for multi-host loaders — those must go through
    GlobalBatchLoader's process-local assembly instead.
    """

    def __init__(self, loader, prefetch: int = 2, sharding=None):
        self.loader = loader
        self.prefetch = max(1, prefetch)
        self.sharding = sharding
        self._stage = None

    def set_epoch(self, epoch: int) -> None:
        if hasattr(self.loader, "set_epoch"):
            self.loader.set_epoch(epoch)

    def __len__(self) -> int:
        return len(self.loader)

    def __iter__(self) -> Iterator:
        import jax

        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        done = object()

        if self._stage is None:
            self._stage = _make_stage(self.sharding)

        stop = threading.Event()

        def producer():
            err = None
            try:
                for batch in self.loader:
                    if stop.is_set():
                        break
                    # async dispatch: the transfer is in flight by the time
                    # the consumer's step needs it
                    q.put(self._stage(batch))
            except BaseException as e:
                err = e
            finally:
                q.put((done, err) if err is not None else done)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                if tele_pipe.enabled():
                    # queue depth AT CONSUME time: 0 means the step is
                    # about to stall on the transfer pipeline
                    tele_pipe.add("device_prefetch_qdepth_sum", q.qsize())
                    tele_pipe.add("device_prefetch_qdepth_gets", 1)
                item = q.get()
                if item is done:
                    break
                if isinstance(item, tuple) and len(item) == 2 \
                        and item[0] is done:
                    raise item[1]
                yield item
            t.join()
        except GeneratorExit:
            # abandoned mid-epoch (HYDRAGNN_MAX_NUM_BATCH caps): stop the
            # producer so the rest of the epoch is NOT collated/transferred
            # in the background
            drain_bounded_queue(q, done, stop)
            raise


class ResidentDeviceLoader:
    """Device-resident dataset: transfer every batch to the accelerator ONCE
    (on the first epoch) and replay from device memory thereafter.

    For datasets whose padded batches fit in HBM this removes the
    host->device transfer from the steady-state epoch entirely — a win in
    proportion to how slow the host link is, free otherwise.  Tradeoff: batch COMPOSITION is frozen after epoch 0;
    only the batch ORDER reshuffles per epoch (seeded, deterministic).  The
    reference reshuffles samples into new batches every epoch — enable this
    (HYDRAGNN_RESIDENT_DATASET=1) only when that distinction doesn't matter
    (it rarely does for large datasets; disable for tiny CI-scale runs
    where batch diversity per epoch is load-bearing).

    Because what is staged is ONE plan of the underlying loader (``set_epoch``
    is forwarded only until staging is complete, so the base loader stays at
    the staged epoch and plans it again when asked), every shape the run
    will see is known from sizes alone when that plan is made.  The trainer
    therefore tells the train loader to fit each dispatch group's PadSpec to
    the groups of that plan (``_align_bucket_group(..., fit=True)`` ->
    ``GraphDataLoader.fit_to_groups``) instead of looking it up in the
    quantile ladder; this wrapper itself picks no shape and stages whatever
    it is handed.
    """

    def __init__(self, loader, seed: int = 0, sharding=None):
        self.loader = loader
        self.seed = seed
        self.sharding = sharding  # e.g. NamedSharding for mesh-DP batches
        self._cache: list = []
        self._complete = False
        self._src = None  # persistent underlying iterator while staging
        self._epoch = 0
        self._stage = None

    def set_epoch(self, epoch: int) -> None:
        self._epoch = epoch
        if not self._complete and self._src is None \
                and hasattr(self.loader, "set_epoch"):
            self.loader.set_epoch(epoch)

    def __len__(self) -> int:
        # During a partially-staged epoch (possible only under a capped
        # consumer, e.g. HYDRAGNN_MAX_NUM_BATCH) this is an approximation:
        # the epoch yields remaining-unstaged + previously-staged items.
        # Capped consumers cap by count, so the approximation is harmless.
        return len(self._cache) if self._complete else len(self.loader)

    def __iter__(self) -> Iterator:
        import numpy as np

        if not self._complete:
            # Staging phase, robust to abandoned epochs (e.g.
            # HYDRAGNN_MAX_NUM_BATCH caps): batches stage incrementally into
            # the cache and the underlying iterator PERSISTS across epochs,
            # so an early break never discards staged work.  UNSTAGED
            # batches come FIRST each epoch (then the staged ones replay),
            # so a capped consumer still advances staging every epoch and
            # sees rotating data coverage instead of a frozen prefix; an
            # uncapped epoch yields the full dataset either way.
            if self._stage is None:
                self._stage = _make_stage(self.sharding)
            if self._src is None:
                self._src = iter(self.loader)
            n_prior = len(self._cache)
            for batch in self._src:
                batch = self._stage(batch)
                self._cache.append(batch)
                yield batch
            self._complete = True
            self._src = None
            for batch in self._cache[:n_prior]:
                yield batch
            return
        order = np.random.default_rng(
            self.seed + self._epoch).permutation(len(self._cache))
        for i in order:
            yield self._cache[i]


class PrefetchLoader:
    """Wrap any iterable-of-batches loader with background prefetch."""

    def __init__(self, loader, num_workers: Optional[int] = None,
                 prefetch: int = 4, pin_affinity: Optional[bool] = None):
        self.loader = loader
        if num_workers is None:
            num_workers = int(os.getenv("HYDRAGNN_NUM_WORKERS", "2"))
        self.num_workers = max(1, num_workers)
        self.prefetch = prefetch
        if pin_affinity is None:
            pin_affinity = bool(int(os.getenv("HYDRAGNN_AFFINITY", "0")))
        self.pin_affinity = pin_affinity

    def set_epoch(self, epoch: int) -> None:
        if hasattr(self.loader, "set_epoch"):
            self.loader.set_epoch(epoch)

    def __len__(self) -> int:
        return len(self.loader)

    def __iter__(self) -> Iterator:
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        done = object()
        stop = threading.Event()

        def worker_init():
            if self.pin_affinity and hasattr(os, "sched_setaffinity"):
                width = int(os.getenv("HYDRAGNN_AFFINITY_WIDTH", "2"))
                offset = int(os.getenv("HYDRAGNN_AFFINITY_OFFSET", "0"))
                ident = threading.get_ident() % self.num_workers
                cpus = set(range(offset + ident * width,
                                 offset + (ident + 1) * width))
                try:
                    os.sched_setaffinity(0, cpus)
                except OSError:
                    pass

        def producer():
            err = None
            try:
                plan_fn = getattr(self.loader, "_batch_plan", None)
                collate_fn = getattr(self.loader, "_collate_plan_item", None)
                if plan_fn is not None and collate_fn is not None:
                    # GraphDataLoader protocol: the plan (indices + pad spec
                    # per batch) is cheap; collations run on the pool and are
                    # consumed in PLAN ORDER — parallel but order-preserving.
                    # Order matters: DeviceStackLoader stacks consecutive
                    # batches, which must share a bucket PadSpec.
                    from collections import deque

                    plan = plan_fn()
                    window = self.num_workers + self.prefetch
                    with ThreadPoolExecutor(
                            max_workers=self.num_workers,
                            initializer=worker_init) as pool:
                        futures: deque = deque()
                        idx = 0
                        while (idx < len(plan) or futures) \
                                and not stop.is_set():
                            while idx < len(plan) and len(futures) < window:
                                futures.append(
                                    pool.submit(collate_fn, plan[idx]))
                                idx += 1
                            # q.put blocks when full: backpressure bounds
                            # in-flight batches to window + prefetch
                            q.put(futures.popleft().result())
                else:
                    # arbitrary iterable: sequential background iteration
                    # (still overlaps collation with device compute)
                    for item in self.loader:
                        if stop.is_set():
                            break
                        q.put(item)
            except BaseException as e:  # surfaced in the consumer thread
                err = e
            finally:
                q.put((done, err) if err is not None else done)

        t = threading.Thread(target=producer, daemon=True)
        t.start()
        try:
            while True:
                if tele_pipe.enabled():
                    # depth 0 at consume time = the trainer outran collation
                    tele_pipe.add("prefetch_qdepth_sum", q.qsize())
                    tele_pipe.add("prefetch_qdepth_gets", 1)
                item = q.get()
                if item is done:
                    break
                if isinstance(item, tuple) and len(item) == 2 \
                        and item[0] is done:
                    # producer died: re-raise so a truncated epoch is never
                    # mistaken for a complete one
                    raise item[1]
                yield item
            t.join()
        except GeneratorExit:
            # abandoned mid-epoch (e.g. a single next() for an example
            # batch, or HYDRAGNN_MAX_NUM_BATCH): stop the producer so the
            # rest of the epoch is not collated in the background, then
            # drain the few in-flight items so it can exit
            drain_bounded_queue(q, done, stop)
            raise


# ---------------------------------------------------------------------------
# process-pool collation (reference HydraDataLoader parity: process-level
# workers with CPU affinity, load_data.py:94-204)
# ---------------------------------------------------------------------------

# Registry keyed by loader token, populated in the parent BEFORE its pool
# exists: every worker (even one the executor spawns lazily mid-epoch)
# forks after registration and inherits the mapping.  A plain single-slot
# global would break when several ProcessPrefetchLoader instances
# (train/val/test) interleave pool creation with lazy worker spawning.
_PROC_REGISTRY: dict = {}


def _proc_worker_init(pin_affinity: bool, num_workers: int, slot_counter):
    if pin_affinity and hasattr(os, "sched_setaffinity"):
        width = int(os.getenv("HYDRAGNN_AFFINITY_WIDTH", "2"))
        offset = int(os.getenv("HYDRAGNN_AFFINITY_OFFSET", "0"))
        # shared counter, not pid % n: pids are not contiguous (any fork
        # elsewhere between lazy worker spawns collides two workers onto
        # one CPU range while others sit idle)
        with slot_counter.get_lock():
            slot = slot_counter.value % max(num_workers, 1)
            slot_counter.value += 1
        cpus = set(range(offset + slot * width,
                         offset + (slot + 1) * width))
        try:
            os.sched_setaffinity(0, cpus)
        except OSError:
            pass


def _proc_collate(token, item):
    loader = _PROC_REGISTRY.get(token)
    if loader is None:  # forked before this loader registered — impossible
        raise RuntimeError("collate worker forked before loader registry")
    return loader._collate_index_item(item)


def _shm_export(batch):
    """Worker side of the shared-memory transport: copy every array leaf
    of the collated batch into ONE SharedMemory segment and return the
    compact descriptor (name + per-leaf layout + treedef) — only the
    descriptor crosses the pipe, not the 2-10 MB of batch bytes the
    pickle transport shipped (the reference's analogous loader shares
    via shmem too: adiosdataset.py:406-454)."""
    from multiprocessing import shared_memory

    import jax

    leaves, treedef = jax.tree_util.tree_flatten(batch)
    specs = []
    total = 0
    for lf in leaves:
        if isinstance(lf, np.ndarray):
            a = np.ascontiguousarray(lf)
            total = -(-total // 128) * 128  # align
            specs.append(("a", a.shape, a.dtype.str, total))
            total += a.nbytes
        else:
            specs.append(("p", lf))  # passthrough (None/scalars)
    shm = shared_memory.SharedMemory(create=True, size=max(total, 1))
    for lf, sp in zip(leaves, specs):
        if sp[0] == "a":
            a = np.ascontiguousarray(lf)
            dst = np.ndarray(a.shape, a.dtype, buffer=shm.buf,
                             offset=sp[3])
            dst[...] = a
    name = shm.name
    shm.close()  # parent unlinks after consumption
    # ownership transfers to the parent: unregister from THIS process's
    # resource tracker or it warns about (and double-unlinks) segments
    # the parent already released
    try:
        from multiprocessing import resource_tracker

        resource_tracker.unregister(shm._name, "shared_memory")
    except Exception:  # graftlint: disable=ROB001 (tracker internals vary by python version)
        pass
    return ("__shm__", name, specs, treedef)


def _proc_collate_shm(token, item):
    return _shm_export(_proc_collate(token, item))


def _shm_import(desc):
    """Parent side: attach the segment and rebuild the batch by COPYING
    each leaf out (one memcpy per leaf — still strictly cheaper than the
    pickle transport's serialize + pipe-frame + deserialize of the same
    bytes).  Copy, not views: CPython 3.12's SharedMemory.close()
    succeeds even while numpy views reference the mapping (measured —
    a retained view then segfaults on read), so a zero-copy contract
    would be a crash hazard for any consumer that holds batches."""
    from multiprocessing import shared_memory

    import jax

    _tag, name, specs, treedef = desc
    shm = shared_memory.SharedMemory(name=name)
    try:
        # try/finally: a failure mid-reconstruction (e.g. a corrupt spec or
        # OOM on a leaf copy) must still unlink the segment, or every such
        # batch leaks its full size in /dev/shm for the process lifetime
        leaves = []
        for sp in specs:
            if sp[0] == "a":
                _t, shape, dtype, off = sp
                v = np.ndarray(shape, np.dtype(dtype), buffer=shm.buf,
                               offset=off)
                leaves.append(np.array(v, copy=True))
                del v
            else:
                leaves.append(sp[1])
        return jax.tree_util.tree_unflatten(treedef, leaves)
    finally:
        _shm_release(shm)


def _shm_discard(result):
    """Release the segment behind a worker's shm descriptor WITHOUT
    rebuilding the batch (abandoned-epoch / close() drain path — copying
    bytes nobody will consume is pure waste)."""
    if not (isinstance(result, tuple) and len(result) == 4
            and result[0] == "__shm__"):
        return
    from multiprocessing import shared_memory

    try:
        shm = shared_memory.SharedMemory(name=result[1])
    except FileNotFoundError:  # already released
        return
    _shm_release(shm)


def _drain_inflight(futures, use_shm: bool) -> None:
    """Settle every in-flight collate future: cancel what hasn't started;
    BLOCK on the rest (cancel() returned False — already running or done:
    its segment exists or is about to) and release their segments.  Without
    the block, a worker finishing after shutdown strands its segment in
    /dev/shm for the host's lifetime (the ADVICE shm-leak on abandoned
    epochs)."""
    for f in futures:
        if f.cancel():
            continue
        try:
            result = f.result()
        except Exception:  # graftlint: disable=ROB001 (worker died; nothing to release)
            continue
        if use_shm:
            _shm_discard(result)


def _shm_release(shm):
    # unlink FIRST: frees the name unconditionally; the mapping lives on
    # until the last view drops.  close() raises BufferError while any
    # numpy view still exports the buffer (e.g. a consumer retaining
    # batches) — best-effort, the GC of the views releases the memory.
    try:
        shm.unlink()
    except FileNotFoundError:  # already unlinked by the peer
        pass
    try:
        shm.close()
    except BufferError:
        pass


class ProcessPrefetchLoader:
    """Collation on a FORKED process pool — true parallelism for
    numpy-heavy collate where the thread pool is GIL-bound (round-3
    verdict: single-threaded collate at 103k graphs/s underruns the
    GIN/SAGE chip rates).

    Protocol: the parent builds the epoch's (index-array, PadSpec) plan
    (cheap), workers collate by INDEX against the dataset they inherited
    at fork time (zero pickling of samples; only the finished numpy batch
    crosses the pipe back).  Order-preserving with bounded in-flight
    batches, like PrefetchLoader.  The pool forks lazily on first use and
    persists across epochs — mutating ``loader.samples`` after that is
    not seen by workers (rebuild the loader for a new corpus).

    Select with HYDRAGNN_COLLATE_PROCS=<n> (create_dataloaders wiring).
    OPT-IN for two reasons: (1) measured on this class of host, the
    per-batch pickle/pipe of the collated arrays exceeds the collation
    itself at flagship shapes (docs/PERF.md round 4) — it pays only when
    per-sample work is genuinely heavy; (2) fork-after-JAX-init draws a
    CPython RuntimeWarning (JAX holds threads); the workers only run
    numpy so the known deadlock pattern (locks held across fork) is not
    exercised, but spawn is not an option here (the protocol relies on
    fork inheritance of the dataset).
    """

    def __init__(self, loader, num_workers: Optional[int] = None,
                 prefetch: int = 4, pin_affinity: Optional[bool] = None):
        self.loader = loader
        if num_workers is None:
            num_workers = int(os.getenv("HYDRAGNN_COLLATE_PROCS", "4"))
        self.num_workers = max(1, num_workers)
        self.prefetch = prefetch
        if pin_affinity is None:
            pin_affinity = bool(int(os.getenv("HYDRAGNN_AFFINITY", "0")))
        self.pin_affinity = pin_affinity
        self._pool = None
        self._inflight = None
        self._use_shm = True

    def set_epoch(self, epoch: int) -> None:
        if hasattr(self.loader, "set_epoch"):
            self.loader.set_epoch(epoch)

    def __len__(self) -> int:
        return len(self.loader)

    def _ensure_pool(self):
        if self._pool is None:
            import multiprocessing as mp
            from concurrent.futures import ProcessPoolExecutor

            self._token = id(self.loader)
            _PROC_REGISTRY[self._token] = self.loader
            ctx = mp.get_context("fork")
            self._pool = ProcessPoolExecutor(
                max_workers=self.num_workers,
                mp_context=ctx,
                initializer=_proc_worker_init,
                initargs=(self.pin_affinity, self.num_workers,
                          ctx.Value("i", 0)))
        return self._pool

    def __iter__(self) -> Iterator:
        from collections import deque

        plan = self.loader._index_plan()
        pool = self._ensure_pool()
        window = self.num_workers + self.prefetch
        # shared-memory transport (default): only a descriptor crosses
        # the pipe; the parent copies the batch out of the segment and
        # releases it immediately.  HYDRAGNN_COLLATE_SHM=0 restores the
        # pickle/pipe transport.
        use_shm = os.getenv("HYDRAGNN_COLLATE_SHM", "1") not in (
            "0", "false", "False")
        self._use_shm = use_shm
        fn = _proc_collate_shm if use_shm else _proc_collate
        # exposed on self so close() can settle an abandoned epoch's
        # still-running collations before pool shutdown
        futures: deque = deque()
        self._inflight = futures
        idx = 0
        try:
            while idx < len(plan) or futures:
                while idx < len(plan) and len(futures) < window:
                    futures.append(pool.submit(
                        fn, self._token, plan[idx]))
                    idx += 1
                out = futures.popleft().result()
                batch = _shm_import(out) if use_shm else out
                if tele_pipe.enabled():
                    # collate accounting must happen in the PARENT: the
                    # workers' module-global counters live in forked
                    # copies the epoch snapshot never sees
                    tele_pipe.add(
                        "collate_bytes", tele_pipe.batch_nbytes(batch))
                    tele_pipe.add("collate_batches", 1)
                yield batch
        finally:
            # ANY abnormal exit leaves futures in flight — an abandoned
            # epoch (GeneratorExit) or a worker error re-raised by
            # .result() above.  Settle every one: cancel the unstarted,
            # block on the running/done (their segments are real) and
            # unlink, so /dev/shm does not leak on either path.
            if futures:
                _drain_inflight(futures, use_shm)
                futures.clear()
            if self._inflight is futures:
                self._inflight = None

    def close(self):
        if self._pool is not None:
            # an abandoned epoch may still have collations in flight:
            # settle them (blocking on the uncancellable ones) and release
            # their segments BEFORE shutdown — shutdown alone neither waits
            # nor unlinks
            inflight = getattr(self, "_inflight", None)
            if inflight:
                _drain_inflight(list(inflight), getattr(
                    self, "_use_shm", True))
                self._inflight = None
            self._pool.shutdown(wait=False, cancel_futures=True)
            self._pool = None
            # drop the registry's strong reference so the dataset can be
            # collected (long-lived sweep processes build many loaders)
            _PROC_REGISTRY.pop(getattr(self, "_token", None), None)
