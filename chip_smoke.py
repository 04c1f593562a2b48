#!/usr/bin/env python3
"""chip_smoke.py — quickest proof that the system still starts on the chip.

ONE process drives the main path once through the entry points a user
calls, at SchNet-QM9 width (examples/qm9/schnet_qm9_chip.json), and then
checks every fused kernel against the composed float32 reference:

  device   name the device JAX reports; anything but the platform asked
           for (default: tpu) exits non-zero at once, before any work
  data     seeded QM9-shaped molecules written as raw XYZ files, so the
           normal raw -> serialized -> radius-graph -> loader path runs
  train    hydragnn_tpu.run_training, fused aggregation backend, float32,
           AdamW, JSONL telemetry: finite falling losses, scan-K dispatch
           and device residency chosen by themselves, the CFConv op on
           the fused path in the manifest, zero fused_fallback events
  predict  hydragnn_tpu.run_prediction on the checkpoint just written
  serve    InferenceEngine.from_config + InferenceServer on port 0,
           /predict over loopback, answers against run_prediction's
  kernels  for every arch in models.create.ALL_ARCHS one full train step
           (forward, backward, AdamW) under ``fused`` — compiled by
           Mosaic, nothing interpreted — against the same step under
           ``scatter``: tightly with both at
           jax.default_matmul_precision("highest"), and as shipped
  summary  one JSON line with everything measured (it ends
           ``"claim": null``), then as the LAST stdout line the verdict
           ``{"ok": ..., "device": {"platform", "kind", "count"}}`` with
           exactly those keys.  Exit 0 only if every stage passed: a
           failed stage ends the run there, ``"ok": false``, exit 1.

With more than one local device the train stage takes the data-parallel
mesh path by itself; the script then also checks that the train state
lives on every device, and ``--compare-with one_chip_summary.json`` holds
the epoch losses to the one-chip run's.

The only other mode is the explicit ``--platform cpu --tiny`` dry run
(tiny widths, two archs, Pallas interpret mode): a flag the caller
passes, never something the script falls to, and every line it prints
says ``cpu``.  This script states no speed: seconds per stage are there
to budget the run, not to quote.
"""

from __future__ import annotations

import argparse
import contextlib
import copy
import dataclasses
import json
import os
import shutil
import sys
import time
import traceback
import urllib.request

_HERE = os.path.dirname(os.path.abspath(__file__))

# -- tolerances, each with its reason ---------------------------------------

# fused vs composed, BOTH traced under default_matmul_precision("highest"):
# every matmul (XLA's and Mosaic's) is then true float32, so the two paths
# differ only by summation order and transcendental rounding (measured on
# the v5e: at most 3.6e-6 over the nine stacks), and a kernel that slipped
# an f32 operand to bf16 (relative error 2^-9 ~ 2e-3 per product) lands
# well above the bound.
TOL_F32 = 1e-4
# the programs as users run them (default matmul precision, where f32
# matmuls run through bf16 passes; the bf16 row in bf16): fused against
# composed AT THE SAME PRECISION AND DTYPE.  Both sides then round the
# same operands in the layers they share, so what is left is the kernels'
# own reduced-precision arithmetic, and the bound is the repo's own
# bf16-vs-f32 step-0 acceptance bound (train/trainer.py:_TRAIN_DTYPE_TOL,
# relative drift of loss and global grad norm).  How far either path sits
# from the float32 truth at that precision is the MODEL's conditioning on
# this input (DimeNet: 5-7e-2 on atoms as close as 0.1 A) — recorded per
# row as ``drift_default``, not gated.
TOL_DEFAULT = 0.05
# run_prediction (composed path) vs the trainer's last test epoch (fused
# path) on the same state and test split, and server answers vs
# run_prediction's at a different PadSpec (bit-identity is the contract at
# the SAME PadSpec only, docs/SERVING.md "Parity contract"): float32
# programs at default matmul precision that differ in which side rounds a
# gathered row to bf16.
TOL_EVAL_REL = 2e-2
TOL_SERVE_ABS = 5e-3
# one-chip vs four-chip epoch losses at the same global batch and seed.
# The same graphs take the same optimizer steps either way; what differs is
# the accumulation order (micro-batches of 64 against one batch of 256),
# and training down the steep first epochs under Adam amplifies any
# difference by about two orders of magnitude per epoch.  Measured with
# this config, data and seed: in true float32 (CPU, composed backend) the
# three train-epoch losses differ by 7e-8, 1e-6, 2e-4 and the last test
# loss by 6e-4; on the v5e at default matmul precision — where a 1e-7
# difference can move an operand across a bf16 rounding boundary, so the
# floor is the bf16 one — by 6e-5, 1.8e-3, 1.7e-2 and 5.2e-2.  So the
# FIRST epoch's train loss (the mean over steps 0-31, where the runs
# still coincide) is held tight — a sharding bug (a device fed another's
# graphs, a shard's gradient dropped) moves it by far more — and the
# later points only to the scale of that drift.
TOL_MULTICHIP_FIRST = 1e-3
TOL_MULTICHIP_REL = 0.1


class SmokeFailure(AssertionError):
    """A stage's check did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(info, msg: str) -> None:
    """One stdout line, prefixed with the platform that produced it."""
    print(f"[{info['platform']}] {msg}", flush=True)


def _finite(xs) -> bool:
    import numpy as np

    return bool(np.all(np.isfinite(np.asarray(xs, dtype=np.float64))))


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


# -- data -------------------------------------------------------------------


def synthesize_molecules(n_mol: int, seed: int, n_lo: int, n_hi: int):
    """Seeded QM9-shaped molecules: the geometry and Morse pair energies
    of examples/qm9/train.py:synthesize_molecules, ``n_lo..n_hi`` atoms."""
    import numpy as np

    from hydragnn_tpu.graph.neighborlist import radius_graph

    rng = np.random.RandomState(seed)
    mols = []
    while len(mols) < n_mol:
        n = int(rng.randint(n_lo, n_hi + 1))
        z = rng.choice([1, 6, 7, 8, 9], size=n,
                       p=[0.5, 0.3, 0.08, 0.1, 0.02])
        pos = rng.rand(n, 3) * (n ** (1 / 3)) * 1.2
        ei = radius_graph(pos, 2.0, max_neighbours=12)
        if ei.shape[1] == 0:
            continue
        d = np.linalg.norm(pos[ei[0]] - pos[ei[1]], axis=1)
        w = 0.1 * (z[ei[0]] + z[ei[1]])
        energy = 0.5 * float((w * ((1 - np.exp(-(d - 1.0))) ** 2 - 1.0))
                             .sum()) / n
        mols.append((z, pos, energy))
    return mols


def write_xyz_dataset(mols, dirpath: str) -> None:
    """One extended-XYZ file per molecule in the layout
    hydragnn_tpu.data.raw.XYZDataset parses (line 2 = graph features)."""
    os.makedirs(dirpath, exist_ok=True)
    for i, (z, pos, energy) in enumerate(mols):
        rows = "\n".join(f"{int(zz)} {p[0]:.6f} {p[1]:.6f} {p[2]:.6f}"
                         for zz, p in zip(z, pos))
        with open(os.path.join(dirpath, f"mol_{i:06d}.xyz"), "w") as f:
            f.write(f"{len(z)}\n{energy:.10f}\n{rows}\n")


# -- stages -----------------------------------------------------------------


def stage_device(args):
    from hydragnn_tpu.utils.runtime import device_info, setup_compile_cache

    cache_dir = setup_compile_cache()
    info = device_info()
    info["compile_cache_dir"] = cache_dir
    info["compile_cache_entries_at_start"] = (
        len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0)
    line = (f"[{info['platform']}] device: kind={info['device_kind']} "
            f"count={info['device_count']} jax={info['jax']} "
            f"jaxlib={info['jaxlib']} libtpu={info['libtpu']} "
            f"compile_cache={cache_dir} "
            f"(entries at start: {info['compile_cache_entries_at_start']})")
    if info["platform"] != args.platform:
        # the chip is never hidden: no stage has run, NOTHING is printed on
        # stdout, and the exit code says so
        print(f"{line}\nchip_smoke: asked for platform {args.platform!r} "
              f"but JAX reports {info['platform']!r}; refusing to run (the "
              "only CPU mode is the explicit --platform cpu --tiny dry run)",
              file=sys.stderr)
        sys.exit(3)
    print(line, flush=True)
    return info


def stage_data(args, sizes, config):
    mols = synthesize_molecules(sizes["n_mol"], args.seed,
                                sizes["atoms_lo"], sizes["atoms_hi"])
    raw_dir = config["Dataset"]["path"]["total"]
    write_xyz_dataset(mols, raw_dir)
    n_atoms = [len(m[0]) for m in mols]
    return {"molecules": len(mols), "atoms_min": min(n_atoms),
            "atoms_max": max(n_atoms), "raw_dir": os.path.abspath(raw_dir),
            "format": config["Dataset"]["format"]}


def _read_events(logs_dir: str):
    """Every JSONL telemetry record the run wrote under ``logs_dir``."""
    events = []
    for root, _dirs, files in os.walk(logs_dir):
        for fn in files:
            if fn == "events.jsonl":
                with open(os.path.join(root, fn)) as f:
                    events += [json.loads(ln) for ln in f if ln.strip()]
    return events


def stage_train(args, config, info):
    import jax

    import hydragnn_tpu

    state, history, fconfig = hydragnn_tpu.run_training(
        copy.deepcopy(config), logs_dir="./logs/", seed=args.seed)
    pipe = history["pipeline"]
    say(info, f"train: pipeline={json.dumps(pipe)}")
    losses = {k: [float(v) for v in history[k]]
              for k in ("train", "val", "test")}
    n_epoch = config["NeuralNetwork"]["Training"]["num_epoch"]
    check(len(losses["train"]) == n_epoch,
          f"expected {n_epoch} epochs, history has {len(losses['train'])}")
    check(all(_finite(v) for v in losses.values()),
          f"non-finite epoch loss: {losses}")
    check(losses["train"][-1] < losses["train"][0],
          f"train loss did not fall: {losses['train']}")

    events = _read_events("./logs/")
    starts = [e for e in events if e.get("event") == "run_start"]
    manifests = [e for e in events if e.get("event") == "manifest"]
    check(len(starts) == 1 and len(manifests) == 1,
          f"expected one run_start and one manifest record, got "
          f"{len(starts)} / {len(manifests)}")
    for rec in (starts[0], manifests[0]):
        for key in ("platform", "device_kind", "device_count"):
            check(rec.get(key) == info[key],
                  f"telemetry {rec['event']} names {key}={rec.get(key)!r}, "
                  f"the process got {info[key]!r}")
    dispatch = manifests[0].get("aggr_dispatch") or {}
    fallbacks = [e for e in events if e.get("event") == "health"
                 and e.get("kind") in ("fused_fallback", "egcl_fallback")]
    say(info,
        f"train: aggr_dispatch={json.dumps(dispatch)} "
        f"fused_fallback_events={len(fallbacks)}")
    check(dispatch.get("gather_mul:fused", 0) > 0
          and "gather_mul:scatter" not in dispatch,
          f"the CFConv op (gather_mul) is not on the fused path: {dispatch}")
    check(not fallbacks, f"fused_fallback health events: {fallbacks}")

    n_dev = info["device_count"]
    if not args.tiny:
        # what an out-of-the-box job gets at this size: both on, unasked
        check(pipe["steps_per_dispatch"] > 1 and pipe["auto_selected"],
              f"scan-K dispatch was not auto-selected: {pipe}")
        check(pipe["resident"], f"device residency is off: {pipe}")
    mesh = {}     # what the pipeline record does not already say
    if n_dev > 1:
        check(pipe["use_mesh_dp"] and pipe["dp_extent"] == n_dev,
              f"{n_dev} devices but the run did not take the mesh path "
              f"over all of them: {pipe}")
        held = set()
        for leaf in jax.tree_util.tree_leaves(state.params):
            check(len(leaf.sharding.device_set) == n_dev,
                  f"a param leaf lives on {len(leaf.sharding.device_set)} "
                  f"of {n_dev} devices")
            for shard in leaf.addressable_shards:
                check(shard.data.size > 0 and not shard.data.is_deleted(),
                      "a param shard holds no live buffer")
                held.add(shard.device.id)
        check(len(held) == n_dev,
              f"live param buffers on devices {sorted(held)} only")
        mesh["devices_holding_state"] = sorted(held)
    if args.compare_with:
        with open(args.compare_with) as f:
            ref = json.load(f)
        check(ref["device"]["count"] == 1 and ref["seed"] == args.seed
              and ref["tiny"] == args.tiny,
              "--compare-with must name a one-chip summary of the same "
              "seed and size")
        devs = {k: [_rel(a, b) for a, b in
                    zip(mine, ref["train"]["losses"][k])]
                for k, mine in losses.items()}
        first, worst = devs["train"][0], max(max(v) for v in devs.values())
        mesh["rel_dev_vs_one_chip"] = devs
        say(info,
            f"train: epoch losses vs one-chip run: "
            f"first-epoch train loss rel dev {first:.3e} (tol "
            f"{TOL_MULTICHIP_FIRST}), max over all epochs and splits "
            f"{worst:.3e} (tol {TOL_MULTICHIP_REL}); per split "
            f"{json.dumps(devs)}")
        check(first <= TOL_MULTICHIP_FIRST and worst <= TOL_MULTICHIP_REL,
              f"epoch losses deviate from the one-chip run: {devs}")
    return {"losses": losses, "pipeline": pipe, "aggr_dispatch": dispatch,
            "fused_fallback_events": len(fallbacks), "mesh": mesh}, fconfig


def stage_predict(args, config, info, train_out):
    import numpy as np

    import hydragnn_tpu

    error, _tasks, true_v, pred_v = hydragnn_tpu.run_prediction(
        copy.deepcopy(config), logs_dir="./logs/", seed=args.seed)
    pred = np.asarray(pred_v[0])
    check(_finite(pred) and _finite(error), "non-finite prediction")
    check(pred.shape == np.asarray(true_v[0]).shape and pred.shape[0] > 0,
          f"prediction shape {pred.shape} vs labels "
          f"{np.asarray(true_v[0]).shape}")
    last_test = train_out["losses"]["test"][-1]
    dev = _rel(float(error), last_test)
    say(info,
        f"predict: test error {float(error):.8f} vs "
        f"trainer's last test epoch {last_test:.8f} (rel dev {dev:.3e}, "
        f"tol {TOL_EVAL_REL})")
    check(dev <= TOL_EVAL_REL,
          f"run_prediction's test error {error} != trainer's {last_test}")
    return {"test_error": float(error), "trainer_last_test": last_test,
            "rel_dev": dev, "n_test": int(pred.shape[0])}, pred


def _http_json(url: str, body=None, timeout: float = 120.0):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read())


def stage_serve(args, sizes, config, fconfig, info, pred_ref):
    import numpy as np

    from hydragnn_tpu.data.load_data import dataset_loading_and_splitting
    from hydragnn_tpu.serve import (InferenceEngine, InferenceServer,
                                    ServingConfig)

    # the test split exactly as run_prediction built it (same seed)
    _tr, _va, test_loader, _cfg = dataset_loading_and_splitting(
        copy.deepcopy(config), seed=args.seed)
    samples = test_loader.samples
    head = fconfig["NeuralNetwork"]["Variables_of_interest"][
        "output_names"][0]

    serving = ServingConfig.from_section(fconfig.get("Serving"))
    serving.buckets = tuple(sizes["serve_buckets"])
    serving.port = 0
    engine = InferenceEngine.from_config(fconfig, logs_dir="./logs/",
                                         serving=serving)
    server = InferenceServer(engine, serving=serving).start()
    try:
        base = f"http://{serving.host}:{server.port}"
        warm = _http_json(base + "/metrics")["engine"]
        worst, answered = 0.0, 0
        for i in range(sizes["serve_requests"]):
            s = samples[i]
            body = {"x": s.x.tolist(), "pos": s.pos.tolist()}
            if i % 2 == 0:
                # odd requests leave the radius graph to the server
                body["edge_index"] = s.edge_index.tolist()
            ans = _http_json(base + "/predict", body)
            got = np.asarray(ans["heads"][head], np.float64).reshape(-1)
            check(_finite(got), f"request {i}: non-finite answer")
            worst = max(worst, float(np.max(np.abs(
                got - np.asarray(pred_ref[i], np.float64).reshape(-1)))))
            answered += 1
        after = _http_json(base + "/metrics")["engine"]
    finally:
        server.shutdown(drain=True)
    misses = after["misses"] - warm["misses"]
    say(info,
        f"serve: {answered} /predict answers over "
        f"loopback, max |answer - run_prediction| {worst:.3e} (tol "
        f"{TOL_SERVE_ABS}), buckets {list(serving.buckets)}, warmup "
        f"compiles {after['warmup_compiles']}, cache misses after "
        f"warm-up {misses}, donated batch arg: {bool(engine._donate)}")
    check(worst <= TOL_SERVE_ABS,
          f"server answers deviate {worst:.3e} from run_prediction")
    check(misses == 0 and after["misses"] == 0,
          f"cache misses after warm-up: {after}")
    check(after["hits"] >= answered, f"fewer cache hits than answers: {after}")
    return {"requests": answered, "max_abs_dev": worst,
            "bit_identical": worst == 0.0,
            "cache_misses_after_warmup": misses,
            "warmup_compiles": after["warmup_compiles"],
            "donated": bool(engine._donate)}, samples


# -- kernel stage -----------------------------------------------------------


def _kernel_samples(arch: str, samples, hidden: int, radius: float,
                    seed: int):
    """The kernel batch's graphs for ``arch``.  PNA and CGCNN consume edge
    lengths as edge features; CGCNN's convolution preserves the feature
    width, so its node feature is a seeded per-element vector at the
    hidden width (the analog of its published per-element input table)."""
    import numpy as np

    from hydragnn_tpu.graph.batch import GraphSample
    from hydragnn_tpu.graph.neighborlist import edge_lengths

    if arch not in ("PNA", "CGCNN"):
        return samples
    table = np.random.RandomState(seed).rand(16, hidden).astype(np.float32)
    out = []
    for s in samples:
        x = s.x
        if arch == "CGCNN":
            # x is the min-max-normalized atomic number: a stable key
            x = table[np.round(s.x[:, 0] * 15).astype(int)]
        ea = (edge_lengths(s.pos.astype(np.float64), s.edge_index)
              / radius).astype(np.float32).reshape(-1, 1)
        out.append(GraphSample(x=x, pos=s.pos, edge_index=s.edge_index,
                               edge_attr=ea, graph_y=s.graph_y,
                               node_y=s.node_y))
    return out


def _kernel_model_config(arch: str, hidden: int, dtype: str, layers: int,
                         arch_sec, samples):
    import numpy as np

    from hydragnn_tpu.models.base import GraphHeadCfg, ModelConfig

    deg = np.concatenate([
        np.bincount(s.edge_index[1], minlength=s.num_nodes)
        for s in samples]).astype(np.float64)
    return ModelConfig(
        model_type=arch,
        input_dim=int(samples[0].x.shape[1]),
        # CGConv preserves the feature width: its width IS the input's
        hidden_dim=int(samples[0].x.shape[1]) if arch == "CGCNN" else hidden,
        output_dim=(1,), output_type=("graph",),
        graph_head=GraphHeadCfg(2, hidden, 2, (hidden, hidden)),
        node_head=None, task_weights=(1.0,), num_conv_layers=layers,
        compute_dtype=dtype,
        edge_dim=1 if samples[0].edge_attr is not None else None,
        equivariance=arch == "EGNN",
        # attention dropout 0: the repo's own recipe at this GAT width
        # (models/create.py warns about the default 0.25)
        dropout=0.0,
        radius=float(arch_sec["radius"]),
        max_neighbours=int(arch_sec["max_neighbours"]),
        max_degree=int(arch_sec["max_neighbours"]),
        pna_avg_deg_log=float(np.log(deg + 1).mean()),
        pna_avg_deg_lin=float(deg.mean()),
        num_gaussians=int(arch_sec["num_gaussians"]), num_filters=hidden,
        # DimeNet++ block sizes (PyG defaults, recalled: int 64, basis 8,
        # spherical 7, radial 6, envelope 5, 1 before / 2 after skip)
        envelope_exponent=5, num_before_skip=1, num_after_skip=2,
        num_radial=6, num_spherical=7, basis_emb_size=8, int_emb_size=64,
        out_emb_size=hidden)


def _kernel_batch(arch: str, samples, backend: str):
    """Collate ``samples`` under ``backend`` (the fused backend makes
    collate attach the sender-sort marker the kernels dispatch on)."""
    from hydragnn_tpu.graph.batch import HeadSpec, collate
    from hydragnn_tpu.data.dataloader import pad_spec_for
    from hydragnn_tpu.ops.aggregate import backend_scope

    with backend_scope(backend):
        batch = collate(samples, pad_spec_for(samples, len(samples)),
                        [HeadSpec("energy_per_atom", "graph", 1)])
        if arch == "DimeNet":
            import numpy as np

            from hydragnn_tpu.models.dimenet import (
                DnTriGate, add_dimenet_extras, count_triplets)

            real = np.asarray(batch.edge_mask) > 0
            ei = np.stack([np.asarray(batch.senders)[real],
                           np.asarray(batch.receivers)[real]])
            n_tri = count_triplets(ei, batch.x.shape[0])
            # the static per-dataset gate, as data/load_data.py builds it
            gate = DnTriGate(
                max_edges_per_graph=max(s.num_edges for s in samples))
            batch = add_dimenet_extras(
                batch, max_triplets=-(-(n_tri + 1) // 8) * 8, tri_gate=gate)
    return batch


def _one_step(model, cfg, opt_spec, state, batch, backend: str,
              precision, on_tpu: bool):
    """Trace, lower, compile and run ONE full train step (forward,
    backward, AdamW) under ``backend`` and the given default matmul
    precision.  Returns (loss, grad_norm, custom_calls, compile_s,
    dispatch tally of this trace)."""
    import jax

    from hydragnn_tpu.ops.aggregate import backend_scope
    from hydragnn_tpu.telemetry import pipeline
    from hydragnn_tpu.train.trainer import make_train_step

    ctx = (jax.default_matmul_precision(precision) if precision
           else contextlib.nullcontext())
    before = pipeline.dispatch_snapshot()
    with backend_scope(backend), ctx:
        step = jax.jit(make_train_step(model, cfg, opt_spec,
                                       telemetry_metrics=True))
        lowered = step.lower(state, batch)
        tally = pipeline.dispatch_delta(before, pipeline.dispatch_snapshot())
        text = lowered.as_text()
        calls = text.count("tpu_custom_call")
        if backend == "fused" and on_tpu:
            check(calls > 0, "fused step lowered without a tpu_custom_call "
                             "(nothing was handed to Mosaic)")
        t0 = time.perf_counter()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0
        _new_state, metrics = compiled(state, batch)
        loss = float(metrics["loss"])
        gnorm = float(metrics["grad_norm"])
    return loss, gnorm, calls, compile_s, tally


def _kernel_row(arch, hidden, dtype, layers, arch_sec, samples, seed,
                info):
    import jax

    from hydragnn_tpu.models.create import create_model
    from hydragnn_tpu.ops.aggregate import backend_scope
    from hydragnn_tpu.telemetry import pipeline
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.trainer import create_train_state

    on_tpu = info["platform"] == "tpu"
    ksamples = _kernel_samples(arch, samples, hidden,
                               float(arch_sec["radius"]), seed)
    cfg = _kernel_model_config(arch, hidden, dtype, layers, arch_sec,
                               ksamples)
    # the reference: same params, composed segment ops, float32 compute,
    # every matmul at highest precision
    cfg_ref = dataclasses.replace(cfg, compute_dtype="float32")
    model, model_ref = create_model(cfg), create_model(cfg_ref)
    opt_spec = select_optimizer({"type": "AdamW", "learning_rate": 1e-3})
    pipeline.pop_fallbacks("fused")   # this row's gate decisions only
    b_fused = _kernel_batch(arch, ksamples, "fused")
    b_plain = _kernel_batch(arch, ksamples, "scatter")
    check("edge_perm_sender" in b_fused.extras,
          "collate did not attach the fused-kernel marker")
    with backend_scope("scatter"):
        # f32 master params made on the composed path: init must not
        # depend on the path under test
        state = create_train_state(model_ref, b_plain, opt_spec, seed=seed)
    b_fused, b_plain = jax.device_put(b_fused), jax.device_put(b_plain)

    ref_loss, ref_gn, _c, ref_compile_s, _t = _one_step(
        model_ref, cfg_ref, opt_spec, state, b_plain, "scatter", "highest",
        on_tpu)
    check(_finite([ref_loss, ref_gn]), "non-finite reference step")
    row = {"arch": arch, "hidden": hidden, "dtype": dtype,
           "conv_layers": layers, "graphs": len(ksamples),
           "ref_loss": ref_loss, "ref_grad_norm": ref_gn}
    # as shipped: default matmul precision, the row's own compute dtype
    loss, gn, calls, compile_s, tally = _one_step(
        model, cfg, opt_spec, state, b_fused, "fused", None, on_tpu)
    gated = pipeline.pop_fallbacks("fused")
    loss_c, gn_c, _c, _s, _t = _one_step(
        model, cfg, opt_spec, state, b_plain, "scatter", None, on_tpu)
    check(_finite([loss, gn, loss_c, gn_c]), "non-finite as-shipped step")
    row.update({
        "dispatch": tally,
        "backend": pipeline.dispatch_summary(tally),
        "fused_fallbacks": gated,
        "tpu_custom_calls": calls,
        "interpreted": not on_tpu,
        "compile_s": round(compile_s, 2),
        "dev_default": max(_rel(loss, loss_c), _rel(gn, gn_c)),
        "drift_default": {
            "fused": max(_rel(loss, ref_loss), _rel(gn, ref_gn)),
            "composed": max(_rel(loss_c, ref_loss), _rel(gn_c, ref_gn))},
    })
    devs = [("dev_default", TOL_DEFAULT)]
    if dtype == "float32":
        # the tight comparison: both sides true float32
        loss_h, gn_h, _c, compile_h, _t = _one_step(
            model, cfg, opt_spec, state, b_fused, "fused", "highest", on_tpu)
        row["dev_highest"] = max(_rel(loss_h, ref_loss), _rel(gn_h, ref_gn))
        row["compile_s"] = round(compile_s + compile_h, 2)
        devs.append(("dev_highest", TOL_F32))
    row["ok"] = all(row[k] <= tol for k, tol in devs)
    say(info,
        f"kernels: {arch:8s} h{hidden} {dtype:8s} "
        f"backend={row['backend']:8s} "
        f"{'interpreted' if row['interpreted'] else 'mosaic'} "
        f"custom_calls={calls:3d} compile={row['compile_s']:7.2f}s "
        + " ".join(f"{k}={row[k]:.2e}(tol {tol:g})" for k, tol in devs)
        + " drift_default(fused/composed)="
        + "/".join(f"{row['drift_default'][k]:.2e}"
                   for k in ("fused", "composed"))
        + f" dispatch={json.dumps(tally)}"
        + ("" if row["ok"] else "  <-- FAILED"))
    return row


def stage_kernels(args, sizes, fconfig, info, samples):
    from hydragnn_tpu.models.dimenet import DnTriGate

    arch_sec = fconfig["NeuralNetwork"]["Architecture"]
    n = sizes["kernel_graphs"]
    ksamples = samples[:n]
    # DimeNet's fused triplet kernels engage only where every graph's edges
    # span at most two edge blocks (models/dimenet.py:DnTriGate); at this
    # all-pairs cutoff that is the smaller molecules, so ITS batch is the
    # first n of those — on the full-size ones it takes the composed route
    # by design and there would be no kernel to check
    dn_samples = [s for s in samples
                  if DnTriGate(max_edges_per_graph=s.num_edges).ok][:n]
    rows = []
    for arch in sizes["kernel_archs"]:
        batch_samples = dn_samples if arch == "DimeNet" else ksamples
        check(len(batch_samples) == n,
              f"{arch}: only {len(batch_samples)} of {n} graphs available")
        rows.append(_kernel_row(arch, sizes["kernel_hidden"], "float32",
                                sizes["kernel_layers"], arch_sec,
                                batch_samples, args.seed, info))
    if sizes["wide_hidden"]:
        # the widest fused CFConv gate (ops/scf_mp.py:SCF_F_LIMIT), bf16:
        # the shape closest to the VMEM limit
        rows.append(_kernel_row("SchNet", sizes["wide_hidden"], "bfloat16",
                                sizes["kernel_layers"], arch_sec, ksamples,
                                args.seed, info))
    bad = [f"{r['arch']}-h{r['hidden']}-{r['dtype']}" for r in rows
           if not r["ok"]]
    check(not bad, f"kernel rows outside tolerance: {bad}")
    return rows


# -- driver -----------------------------------------------------------------


def _sizes(tiny: bool):
    if tiny:
        # 100 molecules -> 80 train = 10 batches of 8 on one device and 10
        # stacks of 4 x 2 on four: the same optimizer steps either way
        return {"n_mol": 100, "atoms_lo": 9, "atoms_hi": 12,
                "serve_buckets": (1, 4), "serve_requests": 6,
                "kernel_archs": ("SchNet", "PNA"), "kernel_graphs": 8,
                "kernel_hidden": 16, "kernel_layers": 2, "wide_hidden": 0}
    from hydragnn_tpu.models.create import ALL_ARCHS

    return {"n_mol": 10560, "atoms_lo": 9, "atoms_hi": 29,
            "serve_buckets": (1, 4, 16), "serve_requests": 20,
            "kernel_archs": ALL_ARCHS, "kernel_graphs": 64,
            "kernel_hidden": 128, "kernel_layers": 2, "wide_hidden": 1024}


def _tiny_config(config):
    """The dry run's cut: same path, toy widths (the CPU runs the fused
    kernels in Pallas interpret mode)."""
    arch = config["NeuralNetwork"]["Architecture"]
    arch.update(hidden_dim=16, num_filters=16, num_conv_layers=2,
                num_gaussians=8)
    arch["output_heads"]["graph"].update(
        dim_sharedlayers=16, dim_headlayers=[16, 16])
    config["NeuralNetwork"]["Training"].update(batch_size=8, num_epoch=2)
    return config


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu")
    ap.add_argument("--tiny", action="store_true",
                    help="dry-run sizes; only with --platform cpu")
    ap.add_argument("--workdir", default=os.path.join(_HERE, ".chip_smoke"),
                    help="scratch directory (emptied first)")
    ap.add_argument("--compare-with", default=None, metavar="SUMMARY.json",
                    help="a one-chip run's summary to hold epoch losses to")
    ap.add_argument("--out", default=None,
                    help="also write the summary JSON to this file")
    args = ap.parse_args(argv)
    if (args.platform == "cpu") != args.tiny:
        ap.error("--platform cpu and --tiny go together: the CPU mode is "
                 "the tiny dry run, and the chip runs the real size")
    if args.platform == "cpu":
        os.environ["JAX_PLATFORMS"] = "cpu"
    for opt in ("compare_with", "out"):
        if getattr(args, opt):
            setattr(args, opt, os.path.abspath(getattr(args, opt)))

    if not os.path.isdir(os.path.join(_HERE, "hydragnn_tpu")):
        # the script proves the program beside it, never one found elsewhere
        print(f"chip_smoke: no hydragnn_tpu package beside {__file__}; "
              "nothing to run", file=sys.stderr)
        return 2
    sys.path.insert(0, _HERE)
    t_start = time.perf_counter()
    info = stage_device(args)   # exits non-zero unless the platform matches

    with open(os.path.join(_HERE, "examples", "qm9",
                           "schnet_qm9_chip.json")) as f:
        config = json.load(f)
    if args.tiny:
        config = _tiny_config(config)
    sizes = _sizes(args.tiny)

    shutil.rmtree(args.workdir, ignore_errors=True)
    os.makedirs(args.workdir)
    os.chdir(args.workdir)   # ./dataset ./serialized_dataset ./logs live here
    os.environ["SERIALIZED_DATA_PATH"] = args.workdir

    # the verdict line: exactly these keys, the device as JAX reports it
    verdict = {"ok": False,
               "device": {"platform": info["platform"],
                          "kind": info["device_kind"],
                          "count": info["device_count"]}}
    try:
        summary = _run_stages(args, sizes, config, info, t_start)
    except Exception:
        # a failed stage ends the run: nothing after it runs, the verdict
        # says so and the exit code is non-zero
        traceback.print_exc()
        sys.stderr.flush()
        print(json.dumps(verdict), flush=True)
        return 1
    line = json.dumps(summary)
    if args.out:
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    verdict["ok"] = True
    print(json.dumps(verdict), flush=True)
    return 0


def _run_stages(args, sizes, config, info, t_start):
    seconds = {}

    def timed(name, fn, *a):
        t0 = time.perf_counter()
        out = fn(*a)
        seconds[name] = round(time.perf_counter() - t0, 2)
        say(info,
            f"stage {name}: ok "
            f"({seconds[name]:.1f}s)")
        return out

    data = timed("data", stage_data, args, sizes, config)
    train, fconfig = timed("train", stage_train, args, config, info)
    predict, pred = timed("predict", stage_predict, args, config, info,
                          train)
    serve, samples = timed("serve", stage_serve, args, sizes, config,
                           fconfig, info, pred)
    kernels = timed("kernels", stage_kernels, args, sizes, fconfig, info,
                    samples)

    cache_dir = info["compile_cache_dir"]
    return {
        "ok": True,
        "device": {"platform": info["platform"],
                   "kind": info["device_kind"],
                   "count": info["device_count"]},
        "versions": {k: info[k] for k in ("jax", "jaxlib", "libtpu")},
        "seed": args.seed,
        "tiny": args.tiny,
        "config": "examples/qm9/schnet_qm9_chip.json",
        "compile_cache": {
            "dir": cache_dir,
            "entries_at_start": info["compile_cache_entries_at_start"],
            "entries_at_end": (len(os.listdir(cache_dir))
                               if os.path.isdir(cache_dir) else 0)},
        "seconds": {**seconds,
                    "total": round(time.perf_counter() - t_start, 2)},
        # every compile of the kernel stage (fused programs only), the
        # number that collapses when the persistent cache is warm
        "cold_compile_seconds": round(
            sum(r["compile_s"] for r in kernels), 2),
        "data": data,
        "train": train,
        "predict": predict,
        "serve": serve,
        "kernels": kernels,
        "claim": None,
    }


if __name__ == "__main__":
    sys.exit(main())
