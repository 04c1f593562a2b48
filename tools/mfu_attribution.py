"""Per-op MFU attribution on the compute-dense configs (round-4 VERDICT
item 1).

For each config: measure the real train step (chip loop), pull flops from
XLA's cost model and per-instruction HBM bytes from the optimized HLO
(utils/hlo_bytes), bucket instructions into matmul (MXU) / scatter-gather /
elementwise-fusion classes, and compute each bucket's ROOFLINE lower bound
(bytes / measured bandwidth vs flops / MXU peak).  The residual between the
summed lower bounds and the measured step is what optimization could still
recover; a bucket table where the non-matmul classes dominate at their
bandwidth bound is the "irreducible message-passing traffic" evidence the
verdict asked for.

Configs:
  dense-ladder   SchNet bf16, width x batch sweep (hidden 256..1024,
                 batch 256..2048)
  oc20-dimenet   DimeNet++ at OC20-IS2RE-like shapes (reference
                 DIMEStack.py:79-146): 50-80-atom slabs, radius 6,
                 max_neigh 26, hidden 128

Writes JSON to --out (default /tmp/mfu_attribution.json).
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from hydragnn_tpu.resilience.ckpt_io import atomic_write_json  # noqa: E402

MEASURED_GBPS = 585.0  # docs/PERF.md round-3 marginal bandwidth


def _classify(op: str, name: str) -> str:
    if op in ("dot", "convolution"):
        return "matmul"
    if op in ("scatter", "gather", "sort", "dynamic-slice",
              "dynamic-update-slice"):
        return "scatter-gather"
    if op == "custom-call":
        return "custom-call(pallas)"
    if op == "fusion":
        if "scatter" in name or "gather" in name:
            return "scatter-gather"
        return "fusion(elementwise)"
    return "other"


def attribute(step, state, batch, step_s):
    import jax

    from hydragnn_tpu.utils.hlo_bytes import (
        entry_fusion_boundary_bytes, shape_bytes)

    from hydragnn_tpu.telemetry.flops import require_peak_flops

    mxu_peak = require_peak_flops()  # unknown device: refuse, no default
    compiled = jax.jit(step).lower(state, batch).compile()
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    ma = compiled.memory_analysis()
    ba_bytes = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + 2 * ma.temp_size_in_bytes)
    text = compiled.as_text()
    total_b, per_instr = entry_fusion_boundary_bytes(text)

    # bucket per-instruction bytes by op class; also count dot flops from
    # the cost model (single total — per-dot flops not exposed, so the
    # matmul bucket's TIME bound uses the cost-model flops total)
    op_re = re.compile(r"%(\S+?) = \S+ (\w[\w-]*)\(")
    op_of = {}
    for m in op_re.finditer(text):
        op_of[m.group(1)] = m.group(2)
    buckets = {}
    for name, b in per_instr.items():
        cls = _classify(op_of.get(name, "?"), name)
        buckets.setdefault(cls, [0, 0])
        buckets[cls][0] += b
        buckets[cls][1] += 1
    top = sorted(per_instr.items(), key=lambda kv: -kv[1])[:15]

    bucket_out = {}
    for cls, (b, cnt) in sorted(buckets.items(), key=lambda kv: -kv[1][0]):
        bucket_out[cls] = {
            "hbm_bytes": int(b),
            "instructions": cnt,
            "bandwidth_bound_ms": round(b / (MEASURED_GBPS * 1e9) * 1e3, 3),
        }
    mm_flops_ms = flops / mxu_peak * 1e3
    bound = max(mm_flops_ms,
                bucket_out.get("matmul", {}).get("bandwidth_bound_ms", 0.0))
    lower_bound_ms = bound + sum(
        v["bandwidth_bound_ms"] for k, v in bucket_out.items()
        if k != "matmul")
    return {
        "step_ms": round(step_s * 1e3, 3),
        "flops_per_step": int(flops),
        "achieved_tflops": round(flops / step_s / 1e12, 3),
        "mfu_pct": round(flops / step_s / mxu_peak * 100, 2),
        "hbm_bytes_per_step_buffer_assignment": int(ba_bytes),
        "hbm_gbps": round(ba_bytes / step_s / 1e9, 1),
        "per_class": bucket_out,
        "matmul_flops_bound_ms": round(mm_flops_ms, 3),
        "roofline_lower_bound_ms": round(lower_bound_ms, 3),
        "residual_ms": round(step_s * 1e3 - lower_bound_ms, 3),
        "top_instructions": [
            {"name": n[:80], "op": op_of.get(n, "?"),
             "mbytes": round(b / 1e6, 1)} for n, b in top],
    }


def oc20_dimenet_setup(batch_size=32, hidden=128):
    """OC20-IS2RE-like shapes through the open_catalyst example's own
    slab synthesizer (50-80 atoms, radius 6, DimeNet++)."""
    import importlib.util

    import numpy as np
    import jax

    from hydragnn_tpu.graph.batch import HeadSpec, PadSpec, collate
    from hydragnn_tpu.models.base import GraphHeadCfg, ModelConfig
    from hydragnn_tpu.models.create import create_model
    from hydragnn_tpu.models.dimenet import add_dimenet_extras, count_triplets
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.trainer import create_train_state, make_train_step

    spec = importlib.util.spec_from_file_location(
        "oc_ab", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))),
            "examples", "open_catalyst_2020", "train.py"))
    oc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(oc)
    samples = oc.synthesize_slabs(batch_size, radius=6.0, max_neighbours=26)
    pad = PadSpec.for_batch(batch_size, max(s.num_nodes for s in samples),
                            max(s.num_edges for s in samples))
    batch = collate(samples, pad, [HeadSpec("energy", "graph", 1)])
    real = np.asarray(batch.edge_mask) > 0
    ei = np.stack([np.asarray(batch.senders)[real],
                   np.asarray(batch.receivers)[real]])
    t = count_triplets(ei, batch.x.shape[0])
    batch = add_dimenet_extras(batch, max_triplets=t + 8)
    cfg = ModelConfig(
        model_type="DimeNet", input_dim=2, hidden_dim=hidden,
        output_dim=(1,), output_type=("graph",),
        graph_head=GraphHeadCfg(2, hidden, 2, (hidden, hidden)),
        node_head=None, task_weights=(1.0,), num_conv_layers=4,
        num_radial=6, num_spherical=7, basis_emb_size=8,
        int_emb_size=64, out_emb_size=256, envelope_exponent=5,
        num_before_skip=1, num_after_skip=2, radius=6.0,
        max_neighbours=26)
    model = create_model(cfg)
    opt = select_optimizer({"type": "AdamW", "learning_rate": 1e-3})
    state = create_train_state(model, batch, opt)
    batch = jax.device_put(batch)
    step = make_train_step(model, cfg, opt)
    return state, batch, step


def main():
    import bench

    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="/tmp/mfu_attribution.json")
    ap.add_argument("--phase", default="dense,oc20")
    args = ap.parse_args()
    res = {"mfu_peak_basis_tflops": 197,
           "bandwidth_basis_gbps": MEASURED_GBPS}

    if "dense" in args.phase:
        ladder = {}
        for hidden, bs in ((1024, 512), (1024, 1024), (1024, 2048),
                           (768, 2048), (512, 2048)):
            key = f"SchNet-h{hidden}-b{bs}-bf16"
            try:
                t0 = time.perf_counter()
                state, batch, step, cfg, _s, _h = bench._build(
                    "SchNet", hidden=hidden, dtype="bfloat16",
                    batch_size=bs)
                step_s, state = bench._chip_loop(state, batch, step, 10, 3)
                ladder[key] = attribute(step, state, batch, step_s)
                ladder[key]["graphs_per_sec"] = round(bs / step_s, 1)
                print(f"{key}: {ladder[key]['mfu_pct']}% MFU "
                      f"({time.perf_counter()-t0:.0f}s)", flush=True)
            except Exception as e:  # noqa: BLE001
                ladder[key] = {"error": repr(e)[:200]}
                print(f"{key} FAILED: {e!r}", flush=True)
        res["dense_ladder"] = ladder

    if "dimenet-bench" in args.phase:
        try:
            state, batch, step, cfg, _s, _h = bench._build("DimeNet",
                                                           hidden=64)
            step_s, state = bench._chip_loop(state, batch, step, 10, 3)
            res["dimenet_bench"] = attribute(step, state, batch, step_s)
            res["dimenet_bench"]["graphs_per_sec"] = round(512 / step_s, 1)
            print(f"dimenet-bench: {res['dimenet_bench']['step_ms']} ms",
                  flush=True)
        except Exception as e:  # noqa: BLE001
            res["dimenet_bench"] = {"error": repr(e)[:200]}
            print(f"dimenet-bench FAILED: {e!r}", flush=True)

    if "oc20" in args.phase:
        try:
            state, batch, step = oc20_dimenet_setup()
            step_s, state = bench._chip_loop(state, batch, step, 5, 3)
            res["oc20_dimenet"] = attribute(step, state, batch, step_s)
            res["oc20_dimenet"]["graphs_per_sec"] = round(32 / step_s, 1)
            print(f"oc20-dimenet: {res['oc20_dimenet']['mfu_pct']}% MFU",
                  flush=True)
        except Exception as e:  # noqa: BLE001
            res["oc20_dimenet"] = {"error": repr(e)[:200]}
            print(f"oc20 FAILED: {e!r}", flush=True)

    atomic_write_json(args.out, res)
    print(json.dumps({k: (v if not isinstance(v, dict) else "...")
                      for k, v in res.items()}))


if __name__ == "__main__":
    main()
