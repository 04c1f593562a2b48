#!/usr/bin/env python
"""streambench: throughput + peak-RSS ladder for the streaming data plane.

Measures the windowed gpack loaders (hydragnn_tpu/data/stream/) across a
window ladder in three modes:

- sequential   StreamingGraphLoader, shuffle off (pure decode+collate rate)
- shuffled     StreamingGraphLoader, shuffle on, order=global (the training
               configuration — bit-parity order with the in-memory loader)
- tail         tail-mode loader over an ingest dir that GROWS between
               epochs (manifest re-read + store swap included in the cost)

Every (mode, window) cell runs in its OWN subprocess so ru_maxrss is that
configuration's peak — the bounded-memory claim (resident ~ O(window), not
O(dataset)) is a measured number, not an assertion.  Results land in
BENCH_stream.json.

Usage:
    python tools/streambench.py [--n 4096] [--batch-size 32]
        [--windows 64,256,1024] [--out BENCH_stream.json]
        [--store PATH.gpack]   bench an existing store instead of synthetic
    python tools/streambench.py --selftest      tiny in-tree run, asserts
                                                the resident bound
"""

from __future__ import annotations

import argparse
import json
import os
import resource
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# store construction (synthetic) + byte accounting
# ---------------------------------------------------------------------------


def _make_samples(n: int, seed: int = 11):
    import numpy as np

    from hydragnn_tpu.graph.batch import GraphSample
    from hydragnn_tpu.graph.neighborlist import radius_graph

    rng = np.random.RandomState(seed)
    out = []
    for _ in range(n):
        pos = rng.rand(12, 3).astype(np.float32) * 2.0
        x = rng.rand(12, 1).astype(np.float32)
        out.append(GraphSample(
            x=x, pos=pos, edge_index=radius_graph(pos, 1.2, 12),
            graph_y=x.sum(keepdims=True)[0], node_y=x))
    return out


def _write_store(workdir: str, n: int) -> str:
    from hydragnn_tpu.data.gpack import GpackWriter

    return GpackWriter(os.path.join(workdir, "bench.gpack")).save(
        _make_samples(n))


def _write_ingest(workdir: str, n: int, seal_every: int = 256) -> str:
    from hydragnn_tpu.data.stream.ingest import IngestWriter

    d = os.path.join(workdir, "ingest")
    w = IngestWriter(d, seal_every=seal_every)
    for s in _make_samples(n):
        w.add(s)
    w.close()
    return d


class _CountingStore:
    """Store proxy counting the bytes of every decoded sample (the
    loaders only touch len/sizes/get/sample_view/extra_keys/attrs)."""

    def __init__(self, store):
        self.store = store
        self.bytes = 0

    def __len__(self):
        return len(self.store)

    def sizes(self):
        return self.store.sizes()

    def extra_keys(self):
        return self.store.extra_keys()

    @property
    def attrs(self):
        return self.store.attrs

    def sample_view(self, idx, key):
        return self.store.sample_view(idx, key)

    def get(self, idx):
        s = self.store.get(idx)
        for k in ("x", "pos", "edge_index", "edge_attr", "graph_y",
                  "node_y", "cell"):
            v = getattr(s, k, None)
            if v is not None:
                self.bytes += int(v.nbytes)
        return s


# ---------------------------------------------------------------------------
# child: one (mode, window) measurement in a fresh process
# ---------------------------------------------------------------------------


def run_cell(spec) -> dict:
    import numpy as np

    from hydragnn_tpu.data.gpack import GpackDataset
    from hydragnn_tpu.data.stream.ingest import IngestWriter, open_tail_store
    from hydragnn_tpu.data.stream.loader import StreamingGraphLoader
    from hydragnn_tpu.graph.batch import HeadSpec

    heads = [HeadSpec("e", "graph", 1)]
    mode, window, bs = spec["mode"], spec["window"], spec["batch_size"]
    if mode == "tail":
        store = _CountingStore(open_tail_store(spec["ingest_dir"]))
    else:
        store = _CountingStore(GpackDataset(spec["store"]))
    loader = StreamingGraphLoader(
        store, np.arange(len(store)), heads, bs, window=window,
        shuffle=(mode == "shuffled"), seed=13,
        tail_dir=spec.get("ingest_dir") if mode == "tail" else None)
    epochs = int(spec.get("epochs", 1))
    n_batches = 0
    t0 = time.perf_counter()
    for ep in range(epochs):
        if mode == "tail" and ep == 1 and spec.get("grow"):
            # growth lands between epochs; epoch 1 trains on more data
            w = IngestWriter(spec["ingest_dir"],
                             seal_every=int(spec["grow"]))
            for s in _make_samples(int(spec["grow"]), seed=99 + ep):
                w.add(s)
            w.close()
        loader.set_epoch(ep)
        for _ in loader:
            n_batches += 1
    dt = time.perf_counter() - t0
    n_samples = n_batches * bs
    return {
        "mode": mode,
        "window": window,
        "batch_size": bs,
        "epochs": epochs,
        "batches": n_batches,
        "seconds": round(dt, 4),
        "samples_per_s": round(n_samples / dt, 1) if dt else 0.0,
        "mb_per_s": round(store.bytes / dt / 1e6, 2) if dt else 0.0,
        "read_mb": round(store.bytes / 1e6, 2),
        "resident_peak_samples": int(loader.last_resident_peak),
        "tail_grew": list(loader.tail_grew) if loader.tail_grew else None,
        "peak_rss_mb": round(
            resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024, 1),
    }


def _spawn_cell(spec) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--cell",
         json.dumps(spec)],
        cwd=REPO, env=env, capture_output=True, text=True, check=False)
    if out.returncode != 0:
        raise RuntimeError(
            f"streambench cell {spec['mode']}/W={spec['window']} failed:\n"
            f"{out.stdout}\n{out.stderr}")
    return json.loads(out.stdout.strip().splitlines()[-1])


# ---------------------------------------------------------------------------
# parent: ladder orchestration
# ---------------------------------------------------------------------------


def run_bench(n: int, batch_size: int, windows, out_path: str,
              store_path: str = "", epochs: int = 1,
              grow: int = 0) -> dict:
    workdir = tempfile.mkdtemp(prefix="streambench_")
    if store_path:
        store = store_path
        ingest_dir = ""
    else:
        print(f"streambench: building synthetic store (n={n}) ...")
        store = _write_store(workdir, n)
        ingest_dir = _write_ingest(workdir, n)
    results = []
    for mode in ("sequential", "shuffled", "tail"):
        if mode == "tail" and not ingest_dir:
            continue  # --store benches an immutable file; no tail cell
        for w in windows:
            spec = {"mode": mode, "window": int(w),
                    "batch_size": batch_size, "store": store,
                    "ingest_dir": ingest_dir, "epochs": epochs,
                    "grow": grow if mode == "tail" else 0}
            r = _spawn_cell(spec)
            results.append(r)
            print(f"  {mode:>10}  W={w:<6} {r['samples_per_s']:>9} samp/s "
                  f"{r['mb_per_s']:>8} MB/s  peak_rss={r['peak_rss_mb']} MB "
                  f"resident={r['resident_peak_samples']}")
    doc = {
        "bench": "stream",
        "n_samples": n,
        "batch_size": batch_size,
        "windows": [int(w) for w in windows],
        "results": results,
    }
    from hydragnn_tpu.resilience.ckpt_io import atomic_write_json

    atomic_write_json(out_path, doc)
    print(f"streambench: wrote {out_path}")
    return doc


def run_selftest() -> int:
    doc = run_bench(n=256, batch_size=8, windows=(8, 64),
                    out_path=os.path.join(tempfile.mkdtemp(), "b.json"),
                    epochs=2, grow=64)
    by_key = {(r["mode"], r["window"]): r for r in doc["results"]}
    for (mode, w), r in by_key.items():
        assert r["batches"] > 0, (mode, w)
        # the bounded-memory contract: resident samples never exceed
        # window + one in-flight batch
        assert r["resident_peak_samples"] <= w + doc["batch_size"], r
    tail = by_key[("tail", 8)]
    assert tail["tail_grew"], "tail cell never observed store growth"
    print("streambench: SELFTEST PASS "
          f"({len(doc['results'])} cells, tail grew "
          f"{tail['tail_grew'][0]} -> {tail['tail_grew'][1]})")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=4096,
                    help="synthetic store size (ignored with --store)")
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--windows", default="64,256,1024")
    ap.add_argument("--epochs", type=int, default=1)
    ap.add_argument("--grow", type=int, default=256,
                    help="samples appended between tail-mode epochs")
    ap.add_argument("--store", default="",
                    help="existing .gpack store to bench")
    ap.add_argument("--out", default="BENCH_stream.json")
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--cell", default="", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.cell:
        print(json.dumps(run_cell(json.loads(args.cell))))
        return 0
    if args.selftest:
        return run_selftest()
    windows = [int(w) for w in args.windows.split(",") if w.strip()]
    run_bench(args.n, args.batch_size, windows, args.out,
              store_path=args.store, epochs=args.epochs, grow=args.grow)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    sys.exit(main())
