"""train_epochs_lm — the stock epoch driver for a language-model stack.

Everything that times the job is the stock driver's (``train_epochs.py``,
loaded by path and left untouched): its ``run``, ``assemble``, region
clock, timed loader, profiler window, median epoch rate.  Three things
differ, and they are this file:

* The configuration file carries the model as its public ``config.json``
  writes it (top-level keys, the catalog's names) plus ``share``; the
  program reads ``Architecture.laguna`` / ``Architecture.share``, so
  ``run`` copies them there first.
* ``_run`` is a copy of the stock ``_run`` without the state copy kept
  for the parity step: three times 568 M float32 parameters (weights and
  two AdamW moments) do not fit twice into 16 GB.  The parity step builds
  its weights again from the same seed after the job has returned.  The
  copy also sums the node and routing counters of the step records, and
  checks that no expert layer took its dense path.
* ``correct`` (a) is ``reference_parity``: after the window, the forward
  and backward pass of the TIMED program (``trainer._loss_and_metrics``,
  what ``make_train_step`` differentiates; the optimizer is left out: its
  moments would not fit beside the reference's gradients) on the cell's
  first micro-batch, padded to the bucket the timed loader gives it,
  against the plain reference (``reference/laguna_reference.py``: float32,
  "highest", one document at a time, attention in query blocks) on the
  same seeded weights.  Compared: the loss, the global gradient norm, and
  per parameter group (embedding, each layer's attention, dense
  feed-forward, router, experts, shared expert, head) the norm of the
  gradient and the norm of the DIFFERENCE of the two gradients over the
  reference's norm.  The difference is what catches rounding: noise adds
  to a norm in quadrature and would hide in it.  Two rungs, as the stock
  driver has: the program forced to float32 under "highest" (summation
  order only), and as shipped (bfloat16 products).
"""

from __future__ import annotations

import copy
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


_stock = _load("benchmark_lm_stock_train_epochs",
               os.path.join(_HERE, "train_epochs.py"))
_counts = _load("benchmark_lm_counts",
                os.path.join(os.path.dirname(_HERE), "lm_counts.py"))
_reference = _load("benchmark_lm_reference", os.path.join(
    os.path.dirname(_HERE), "reference", "laguna_reference.py"))

# Limits of the comparison, per rung: on the loss, on the whole gradient's
# difference from the reference's (norm of the difference over the
# reference's norm), and on each parameter group's (its norm, its
# difference).  Each lies between two readings on the v5e (PERF.md, Cells).
#
# Program forced to float32 under "highest" vs the reference: both are true
# float32, and differ by summation order (blocked flash softmax, grouped
# products over sorted rows, sliced feed-forward) and transcendental
# rounding: over ten seeds worst group 6.2e-6, whole gradient 3.8e-6, loss
# 1.2e-7.  As shipped (the nearest precision below) reads 6e-3 on the
# whole gradient: one limit serves all three.
# One seed of ten read 1.8e-4 on one router group: a single node whose
# 10th and 11th expert tie to float32 rounding; the group limit leaves room
# for that (every group reads 4e-3 and more as shipped).
TOL_F32 = {"loss": 2e-4, "grad": 2e-4, "group": 1e-3}
# As shipped (bfloat16 operands, float32 accumulation) vs the reference,
# and the reference with every product's operands rounded to float8_e4m3
# (LAGUNA_PROBE_PRODUCTS) vs itself:
#   loss            5.8e-5 as shipped    3.5e-4 in float8
#   whole gradient  6.7e-3 (ten seeds)   1.2e-1
#   worst group     5.0e-2               3.5e-1 (in float8 every group but
#                                        the head reads 0.11 and more)
# The expert and router groups read 2-5e-2 as shipped where every other
# group reads 4-9e-3: rounding the residual stream swaps the 10th and 11th
# expert of a few nodes, and a swapped expert moves whole rows of those
# gradients.  A dropped term (the gate, the 2.5, a head) moves its group by
# 0.3 and more.
TOL_SHIPPED = {"loss": 1.5e-4, "grad": 2.5e-2, "group": 1e-1}
Q_BLOCK = 1024          # the reference's attention, rows at a time
_HF_SKIP = ("Provenance", "share", "corpus", "expect", "Verbosity",
            "Dataset", "NeuralNetwork", "Telemetry", "Visualization",
            "dry_cpu", "Profile")


def group_of(path: str) -> str:
    """A parameter's group for the comparison, from its tree path
    (``layer_2/moe/experts_w1`` -> ``layer_2.experts``)."""
    parts = path.split("/")
    if parts[0].startswith("layer_"):
        leaf = parts[-1]
        if parts[1] == "moe":
            kind = ("experts" if leaf.startswith("experts_") else
                    "shared" if leaf.startswith("shared_") else "router")
            return f"{parts[0]}.{kind}"
        return f"{parts[0]}.{parts[1]}"
    return "embed" if parts[0] == "embed" else "head"


def reference_parity(job, say):
    import contextlib
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from hydragnn_tpu.graph.batch import collate
    from hydragnn_tpu.models.create import create_model
    from hydragnn_tpu.train.trainer import _loss_and_metrics

    samples = job["trainset"][:job["micro_batch"]]
    loader = job["loaders"][0]
    while not hasattr(loader, "_pick_spec"):
        loader = loader.loader
    spec = loader._pick_spec([samples])
    batch = jax.device_put(collate(samples, spec, job["head_specs"],
                                   *job["slices"]))
    docs = [np.asarray(s.x[:, 0], np.int32) for s in samples]
    arch = job["config"]["NeuralNetwork"]["Architecture"]
    lm, share = arch["laguna"], arch["share"]

    def paths(tree):
        return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
                for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}

    @jax.jit
    def compare(got, ref):
        got, ref = paths(got), paths(ref)
        sq = {}
        for path, r in ref.items():
            acc = sq.setdefault(group_of(path), [0.0, 0.0, 0.0])
            g = got[path].astype(jnp.float32)
            acc[0] += jnp.sum(jnp.square(g))
            acc[1] += jnp.sum(jnp.square(r))
            acc[2] += jnp.sum(jnp.square(g - r))
        return {k: tuple(jnp.sqrt(a) for a in v) for k, v in sq.items()}

    # the weights the trainer started from: the same seed, the same init
    variables = jax.jit(lambda b: job["model"].init(
        {"params": jax.random.PRNGKey(job["seed"]),
         "dropout": jax.random.PRNGKey(job["seed"] + 1)}, b,
        train=False))(batch)
    params = variables["params"]

    def program_grads(cfg, precision):
        model = create_model(cfg)
        ctx = (jax.default_matmul_precision(precision) if precision
               else contextlib.nullcontext())
        with ctx:
            (loss, _aux), grads = jax.jit(jax.value_and_grad(
                lambda p: _loss_and_metrics(
                    model, cfg, p, variables["batch_stats"], batch, True),
                has_aux=True))(params)
        return float(loss), grads

    t0 = time.monotonic()
    loss, grads = program_grads(job["cfg"], None)
    say(f"parity: program as shipped in {time.monotonic() - t0:.1f}s")
    t0 = time.monotonic()
    # every document padded (masked) to one length, the longest's rounded
    # up to the reference's row block: one shape to compile, not one each
    longest = -(-max(len(d) for d in docs) // Q_BLOCK) * Q_BLOCK
    ref_loss, ref_grads = _reference.loss_and_grads(
        params, lm, share, docs, q_block=Q_BLOCK, pad_to=lambda n: longest)
    say(f"parity: reference, {len(docs)} documents one at a time, each "
        f"padded to {longest} tokens, in {time.monotonic() - t0:.1f}s")

    def rung(label, loss, grads, tol):
        groups = {k: [float(x) for x in v]
                  for k, v in compare(grads, ref_grads).items()}
        g_all, r_all, d_all = (
            float(np.sqrt(sum(v[i] ** 2 for v in groups.values())))
            for i in range(3))
        # (deviation, its limit) per compared number
        devs = {"loss": (_stock._rel(loss, ref_loss), tol["loss"]),
                "grad_norm": (_stock._rel(g_all, r_all), tol["grad"]),
                "grad_diff": (d_all / max(r_all, 1e-30), tol["grad"])}
        for k, (g, r, d) in groups.items():
            devs[f"{k}.norm"] = (_stock._rel(g, r), tol["group"])
            devs[f"{k}.diff"] = (d / max(r, 1e-30), tol["group"])
        worst = max(devs, key=lambda k: devs[k][0] / devs[k][1])
        say(f"parity {label}: nearest its limit {worst} "
            f"{devs[worst][0]:.3e} (limit {devs[worst][1]:g}); loss "
            f"{loss:.6f} vs {ref_loss:.6f} ({devs['loss'][0]:.2e}), grad "
            f"norm {g_all:.6g} vs {r_all:.6g}, difference "
            f"{devs['grad_diff'][0]:.3e}")
        say(f"parity {label} by group (norm dev, difference): " + " ".join(
            f"{k}={devs[k + '.norm'][0]:.1e},{devs[k + '.diff'][0]:.1e}"
            for k in sorted(groups)))
        return {"dev": devs[worst][0], "worst": worst,
                "tol": devs[worst][1], "loss": loss, "ref_loss": ref_loss,
                "loss_dev": devs["loss"][0],
                "grad_diff": devs["grad_diff"][0],
                "group_diff_max": max(devs[k + ".diff"][0] for k in groups)}

    out = {"as_shipped": rung("as_shipped", loss, grads, TOL_SHIPPED)}
    del grads
    probe = os.environ.get("LAGUNA_PROBE_PRODUCTS")
    if probe:
        # the builder's reading of "the nearest precision below": the
        # reference with every product's operands rounded to ``probe``
        # against the same reference gradients; refuses nothing
        _reference.PRODUCT_DTYPE = jnp.dtype(probe)
        try:
            low_loss, low_grads = _reference.loss_and_grads(
                params, lm, share, docs, q_block=Q_BLOCK,
                pad_to=lambda n: longest)
        finally:
            _reference.PRODUCT_DTYPE = None
        rung(f"reference_in_{probe}", low_loss, low_grads, TOL_SHIPPED)
        del low_grads
    t0 = time.monotonic()
    cfg32 = dataclasses.replace(job["cfg"], compute_dtype="float32")
    loss32, grads32 = program_grads(cfg32, "highest")
    say(f"parity: program in float32/highest in "
        f"{time.monotonic() - t0:.1f}s")
    out["highest"] = rung("highest", loss32, grads32, TOL_F32)
    out["ok"] = all(out[k]["dev"] <= out[k]["tol"]
                    for k in ("highest", "as_shipped"))
    return out


def run(ctx):
    config = copy.deepcopy(ctx["config"])
    arch = config["NeuralNetwork"]["Architecture"]
    arch["laguna"] = {k: v for k, v in config.items() if k not in _HF_SKIP}
    arch["share"] = config["share"]
    config["corpus"]["params"]["vocab_size"] = config["vocab_size"]
    _stock._run = _run
    return _stock.run({**ctx, "config": config})


def _run(ctx, samples, params, compiles, t_begin):
    """``train_epochs._run`` (see the module docstring for what differs)."""
    import jax
    import numpy as np

    from hydragnn_tpu.config.config import get_log_name_config, save_config
    from hydragnn_tpu.telemetry import MetricsLogger, TelemetryConfig
    from hydragnn_tpu.train.trainer import train_validate_test
    from hydragnn_tpu.utils import tracer as tr
    from hydragnn_tpu.utils.print_utils import setup_log

    say, seed, seconds = ctx["say"], ctx["seed"], ctx["seconds"]
    t0 = time.monotonic()
    config = copy.deepcopy(ctx["config"])
    training = config["NeuralNetwork"]["Training"]
    training["batch_size"] = (int(training["batch_size"])
                              * int(params.get("batch_scale", 1)))
    loader_seed = params.get("loader_seed")
    job = _stock.assemble(config, samples, seed,
                          seed if loader_seed is None else loader_seed)
    job["seed"] = seed
    config = job["config"]
    n_params = sum(int(np.prod(a.shape))
                   for a in jax.tree.leaves(job["state"].params))
    say(f"assembled in {time.monotonic() - t0:.1f}s: n_train={job['n_train']}"
        f" micro_batch={job['micro_batch']} parameters={n_params} "
        f"pad_specs={[p.num_nodes for p in job['loaders'][0].pad_specs]}")
    checks = {}

    logs_dir = os.path.join(ctx["workdir"], "logs")
    shutil.rmtree(ctx["workdir"], ignore_errors=True)
    os.makedirs(logs_dir)
    log_name = get_log_name_config(config)
    setup_log(log_name, logs_dir)
    save_config(config, log_name, logs_dir)
    telemetry = MetricsLogger(
        TelemetryConfig.from_section(config.get("Telemetry")),
        run_name=log_name,
        out_dir=os.path.join(logs_dir, log_name, "telemetry"),
        rank=0, world_size=1)

    tracer = (_stock._Tracer(os.path.join(ctx["workdir"], "trace"),
                             _stock.TRACE_CAP_S) if ctx["trace"] else None)

    def on_epoch(i, t):
        if i >= 2 and t > clock.epoch_starts[1] + seconds:
            raise _stock.WindowClosed
        if i == 2 and tracer is not None:
            tracer.schedule(t, t - clock.epoch_starts[1])

    clock = _stock.RegionClock(on_epoch)
    tr.initialize(timer=True, jax_annotations=bool(ctx["trace"]))
    tr._tracers["bench"] = clock
    train_l, val_l, test_l = job["loaders"]
    timed = _stock.TimedLoader(train_l)
    state = job.pop("state")        # donated to the trainer, not kept
    try:
        train_validate_test(
            job["model"], job["cfg"], state, job["opt_spec"],
            timed, val_l, test_l, config["NeuralNetwork"], log_name,
            config.get("Verbosity", {}).get("level", 0),
            rank=0, world_size=1, logs_dir=logs_dir,
            profile_config=config.get("Profile"), telemetry=telemetry)
    except _stock.WindowClosed:
        pass
    finally:
        if tracer is not None:
            tracer.finish()
        tr.initialize()
    t_done = time.monotonic()
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    live = max((m.get("peak_bytes_in_use") or 0) for m in stats)
    peak = max(max(m.get("peak_bytes_in_use") or 0,
                   m.get("peak_bytes_reserved") or 0) for m in stats)
    say(f"memory_stats[0]: {json.dumps(stats[0])}")
    # the trainer's last state and the staged batches go before the
    # parity step builds weights and two gradient trees of its own
    waits = timed.waits
    del state, telemetry, timed, train_l, val_l, test_l
    gc.collect()
    t0 = time.monotonic()
    checks["parity"] = reference_parity(job, say)
    say(f"parity in {time.monotonic() - t0:.1f}s")

    # -- what was counted ---------------------------------------------------
    starts = clock.epoch_starts
    if len(starts) < 2:
        raise RuntimeError("the job ended before epoch 1 began")
    t_open = starts[1]
    t_close = t_open + seconds
    counted = [e for e in range(1, len(starts) - 1)
               if starts[e + 1] <= t_close]
    events = _stock._read_events(logs_dir)
    manifest = next((e for e in events if e.get("event") == "manifest"), {})
    history = manifest["history"]
    steps_by_epoch = {}
    for ev in events:
        if ev.get("event") == "step":
            steps_by_epoch.setdefault(ev["epoch"], []).append(ev)
    epochs = []
    for e in counted:
        recs = steps_by_epoch.get(e, [])
        moe = [r["moe"] for r in recs if "moe" in r]
        epochs.append({
            "epoch": e, "t0": starts[e], "t1": starts[e + 1],
            "graphs": sum(r["num_graphs"] for r in recs),
            "steps": sum(r["steps_in_dispatch"] for r in recs),
            "skipped": sum(r.get("skipped", 0) for r in recs),
            "nonfinite": sum(1 for r in recs
                             if not np.isfinite(r["loss"])),
            "edges_real": sum(r["padding"]["edges_real"] for r in recs),
            "edges_padded": sum(r["padding"]["padded_edges"] for r in recs),
            "nodes_real": sum(r["padding"]["nodes_real"] for r in recs),
            "nodes_padded": sum(r["padding"]["padded_nodes"] for r in recs),
            "moe_slots_held": sum(m["slots_held"] for m in moe),
            "moe_slots_all": sum(m["slots_all"] for m in moe),
            "moe_dense_steps": sum(m["dense_steps"] for m in moe),
            "moe_load_max_over_mean": (
                float(np.mean([m["load_max_over_mean"] for m in moe]))
                if moe else None),
        })
    wall = (epochs[-1]["t1"] - t_open) if epochs else 0.0
    graphs = sum(e["graphs"] for e in epochs)
    rate = _stock.median_epoch_rate(epochs)
    say(f"window: {len(epochs)} whole epochs, {graphs:.0f} train graphs in "
        f"{wall:.3f}s; job returned {t_done - t_close:.1f}s after the "
        f"window closed (epochs begun: {len(starts)})")
    if epochs and not ctx["dry"]:    # a CPU rehearsal states no rate
        say("epoch seconds: "
            + " ".join(f"{e['t1'] - e['t0']:.4f}" for e in epochs)
            + f"; graphs/s over their sum {graphs / wall:.1f}, "
            f"in the median epoch {rate:.1f}")

    # -- correct --------------------------------------------------------------
    pipe = history["pipeline"]
    say(f"pipeline: {json.dumps(pipe)}")
    expect = {**ctx["config"].get("expect", {}),
              **ctx["traffic"].get("expect", {})}
    for key, want in expect.get("pipeline", {}).items():
        checks[f"pipeline.{key}"] = {"got": pipe.get(key), "want": want,
                                     "ok": pipe.get(key) == want}
    if "steps_per_dispatch_min" in expect:
        checks["steps_per_dispatch"] = {
            "got": pipe["steps_per_dispatch"],
            "ok": pipe["steps_per_dispatch"]
            >= expect["steps_per_dispatch_min"]}
    losses = {k: [float(v) for v in history[k]]
              for k in ("train", "val", "test")}
    finite = all(np.all(np.isfinite(v)) for v in losses.values())
    min_epochs = 1 if ctx["dry"] else _stock.MIN_EPOCHS
    third = min(3, len(losses["train"]) - 1)
    fell = third >= 1 and losses["train"][third] < losses["train"][0]
    checks["losses"] = {"finite": bool(finite), "train": losses["train"][:6],
                        "ok": bool(finite and fell)}
    checks["epochs"] = {"got": len(epochs), "want_min": min_epochs,
                        "ok": len(epochs) >= min_epochs}
    graphs_ok = all(
        job["n_train"] * 0.8 <= e["graphs"] <= job["n_train"]
        for e in epochs)
    checks["graphs_per_epoch"] = {
        "got": [e["graphs"] for e in epochs[:3]], "n_train": job["n_train"],
        "ok": bool(graphs_ok)}
    # the expert layers' dense path is their fallback: counted like
    # fused_fallback, and a cell may require its absence
    dense = sum(e["moe_dense_steps"] for e in epochs)
    routed = sum(e["moe_slots_all"] for e in epochs)
    say(f"routed experts: {sum(e['moe_slots_held'] for e in epochs):.0f} of "
        f"{routed:.0f} slots fell on held experts in the counted epochs; "
        f"{dense:.0f} layer-steps took the dense path")
    checks["moe"] = {"dense_steps": dense, "slots_all": routed,
                     "ok": routed > 0 and (
                         "moe_dense_steps" not in expect
                         or dense == expect["moe_dense_steps"])}
    in_window = [ev for t, ev in compiles if t_open <= t <= t_close]
    checks["compiles_in_window"] = {"got": len(in_window),
                                    "events": in_window[:4],
                                    "ok": not in_window}
    for name, c in checks.items():
        if not c["ok"]:
            say(f"CHECK FAILED {name}: {json.dumps(c, default=str)}")
    correct = all(c["ok"] for c in checks.values())

    doc_lengths = [s.num_nodes for s in job["trainset"]]
    steps_per_epoch = (epochs[0]["steps"] if epochs
                       else max(1, len(doc_lengths) // job["micro_batch"]))
    facts = {
        "epochs": epochs, "pipeline": pipe, "history": losses,
        "spans": clock.spans,
        "loader_waits": waits,
        "memory_peak_bytes": peak or None,
        "memory_live_peak_bytes": live or None,
        "trace_window": tracer.window if tracer else None,
        "trace_dir": tracer.out_dir if tracer else None,
        "trace_error": tracer.error if tracer else None,
        "mono_to_unix_ns": time.time_ns() - time.monotonic() * 1e9,
        "train_module_regex": _stock.TRAIN_MODULES,
        "lm": _counts.lm_facts(ctx["config"], doc_lengths, steps_per_epoch),
    }
    return {
        "correct": bool(correct),
        "attempted": int(sum(e["steps"] for e in epochs)),
        "failed": int(sum(e["skipped"] + e["nonfinite"] for e in epochs)),
        "end_to_end": {
            "train_graphs_per_s": rate,
            "setup_s": t_open - t_begin,
        },
        "facts": facts,
    }
