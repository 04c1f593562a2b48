#!/usr/bin/env python
"""Partition-quality report for graph sharding (docs/SCALING.md §6).

Three modes:

  python tools/partview.py --selftest
      Build synthetic giant graphs (3D lattice + random geometric blob),
      partition with every method x shard count, and print the quality
      table: cut-edge %, halo rows (max/mean), node/edge imbalance,
      halo-buffer padding waste.  The table is the tuning aid for
      ``Training.graph_shard_method`` / ``graph_shard_hops``.

  python tools/partview.py --jsonl logs/<run>/telemetry/events.jsonl
      Render the partition stats a recorded run's `sharding` event
      carries (the same block tools/teleview.py summarizes).

  python tools/partview.py --gpack ... (future: load a real giant graph)

Pure host-side numpy — safe to run anywhere (JAX_PLATFORMS=cpu forced so
an attached TPU is never dialed for an indexing report).
"""

import argparse
import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def _table(rows, header):
    widths = [max(len(str(r[i])) for r in [header] + rows)
              for i in range(len(header))]
    out = ["  ".join(str(h).ljust(w) for h, w in zip(header, widths))]
    for r in rows:
        out.append("  ".join(str(c).ljust(w) for c, w in zip(r, widths)))
    return "\n".join(out)


def _lattice(k, features=4, seed=0):
    # the SAME generator bench.py --giant times, so this report describes
    # the bench's graphs
    from hydragnn_tpu.graph.partition import synthetic_lattice_batch

    return synthetic_lattice_batch(k, features, seed), f"lattice k={k}"


def _blob(n, features=4, seed=1):
    from hydragnn_tpu.graph.batch import GraphSample, HeadSpec, PadSpec, \
        collate
    from hydragnn_tpu.graph.neighborlist import radius_graph

    rng = np.random.RandomState(seed)
    pos = rng.rand(n, 3).astype(np.float32) * (n ** (1 / 3.0))
    ei = radius_graph(pos, radius=1.0, max_neighbours=12)
    s = GraphSample(x=rng.rand(n, features).astype(np.float32), pos=pos,
                    edge_index=ei, node_y=rng.rand(n, 1).astype(np.float32))
    return collate([s], PadSpec(n + 8, ei.shape[1] + 8, 2),
                   [HeadSpec("y", "node", 1)]), f"geometric n={n}"


def _stat_row(name, method, st):
    return [name, method, st["n_shards"], st["hops"],
            st["n_nodes_real"], st["n_edges_real"],
            f"{st['cut_edge_pct']}%", st["halo_rows_max"],
            st["halo_rows_mean"], st["node_imbalance"],
            st["edge_imbalance"], f"{st['halo_waste_pct']}%",
            st["n_local"] + st["n_shards"] * st["halo_pair"]]


_HEADER = ["graph", "method", "D", "hops", "nodes", "edges", "cut",
           "halo_max", "halo_mean", "node_imb", "edge_imb", "buf_waste",
           "rows/dev"]


def selftest(args) -> int:
    from hydragnn_tpu.graph.partition import build_shard_plan

    graphs = [_lattice(12), _blob(1500)]
    rows = []
    for batch, name in graphs:
        for method in ("block", "bfs", "sfc"):
            for d in (int(x) for x in args.shards.split(",")):
                plan = build_shard_plan(batch, d, method=method,
                                        hops=args.hops)
                rows.append(_stat_row(name, method, plan.stats))
    print(_table(rows, _HEADER))
    # the selftest's claim: the sfc order beats the naive block order on
    # cut fraction for BOTH graph classes at D=8, and bfs beats block on
    # the irregular (geometric) graph.  (On a row-major LATTICE the block
    # order is already axis-aligned slabs — near-optimal — and BFS's
    # frontier shells lose to it; that asymmetry is exactly why the
    # method is a knob.)
    by = {}
    for r in rows:
        if r[2] == 8:
            by[(r[0], r[1])] = float(r[6].rstrip("%"))
    names = [name for _, name in graphs]
    ok = all(by[(g, "sfc")] < by[(g, "block")] for g in names) and \
        by[(names[1], "bfs")] < by[(names[1], "block")]
    print(f"\nselftest: sfc beats block on both graphs, bfs on the "
          f"irregular one: {'PASS' if ok else 'FAIL'}")
    return 0 if ok else 1


def from_jsonl(path: str) -> int:
    recs = [json.loads(l) for l in open(path) if l.strip()]
    blocks = []
    for r in recs:
        if r.get("event") == "sharding" and r.get("graph_shard"):
            blocks.append(r["graph_shard"])
        elif r.get("event") == "manifest" and \
                (r.get("sharding") or {}).get("graph_shard"):
            blocks.append(r["sharding"]["graph_shard"])
    if not blocks:
        print("no graph_shard partition stats recorded in", path)
        return 1
    st = blocks[-1]
    print(f"recorded partition ({st.get('backend')} backend, requested "
          f"{st.get('requested', st.get('backend'))}):")
    if st.get("n_local") is None:
        print("  (backend fell back or carries no partition stats)")
        return 0
    print(_table([_stat_row("run", st.get("method", "-"), st)], _HEADER))
    if st.get("fallback"):
        print(f"  WARNING fell back: {st['fallback']}")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--jsonl", help="telemetry events.jsonl of a run")
    ap.add_argument("--shards", default="4,8",
                    help="comma ladder of shard counts (selftest)")
    ap.add_argument("--hops", type=int, default=2)
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest(args)
    if args.jsonl:
        return from_jsonl(args.jsonl)
    ap.print_help()
    return 2


if __name__ == "__main__":
    sys.exit(main())
