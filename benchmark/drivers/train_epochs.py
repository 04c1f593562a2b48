"""train_epochs — run one training job through the real epoch loop and time
whole epochs.

The job is assembled the way ``run_training`` assembles it after data
loading (``hydragnn_tpu/run_training.py:_run_training_dict`` and what
``data/load_data.py:dataset_loading_and_splitting`` derives from the
samples; ``examples/open_catalyst_2020/train.py`` does the same by hand):
split -> DatasetStats -> finalize -> loaders (micro-batch on several
devices, DimeNet's triplet table) -> model -> optimizer -> train state ->
ONE ``train_validate_test`` call with a large ``num_epoch``.  Left out:
raw-file parsing (the corpus plug-in makes the samples), the TensorBoard
writer (its import alone costs ~14 s here), checkpoint restore.

Only epoch boundaries are honest timestamps — the trainer dispatches a
whole epoch without a device->host sync and drains it with one
``device_get`` — so the clock is read where every epoch begins: a tracer
plugged into ``utils/tracer`` sees the trainer's ``train`` region open.
Epoch 0 (trace, compile or cache load, resident staging) is set-up.  The
window opens when epoch 1 begins.  Counted are the whole epochs that ended
inside it, and ``train_graphs_per_s`` is the median over them of an epoch's
train graphs / its wall seconds (train, val, test and the sync; begin to
begin); the first epoch to BEGIN after it has closed is where the job is
stopped, by an exception raised from that same tracer hook, which leaves
``train_validate_test`` through its own ``finally`` (manifest written,
handlers restored).  A SIGTERM through the trainer's preemption handler
would stop it as a scheduler does, but the resume bundle it then saves cost
~30 s of chip time on every run (first chip runs, PR 22) and lies outside
every counted epoch anyway.
"""

from __future__ import annotations

import contextlib
import copy
import glob
import json
import os
import shutil
import threading
import time

# fused vs composed with BOTH traced under default_matmul_precision
# ("highest"): every matmul, XLA's and Mosaic's, is then true float32, the
# two paths differ by summation order and transcendental rounding only
# (at most 3.6e-6 over the nine stacks on the v5e, PR 21), and a kernel
# that slipped an f32 operand to bf16 (2^-9 ~ 2e-3 per product) fails.
TOL_F32 = 1e-4
# as shipped (default precision): fused vs composed at the SAME precision;
# the repo's own bf16-vs-f32 step-0 acceptance bound.
TOL_DEFAULT = 0.05
_BACKEND_ENV = "HYDRAGNN_AGGR_BACKEND"
MIN_EPOCHS = 5          # whole epochs a window must hold (PERF.md, Cells)
TRACE_CAP_S = 4.0       # longest profiler window: ~30 MB of trace
# the jitted train steps as the trace names their programs: the scanned
# one-chip step, the plain one, the scanned DP step
TRAIN_MODULES = r"jit_(scan_step|train_step|multi)\b"


class WindowClosed(Exception):
    """Raised into the trainer when an epoch begins after the window."""


class RegionClock:
    """A ``utils/tracer`` tracer that reads the benchmark's own clock at
    the trainer's region boundaries.  ``spans`` holds (name, start, stop)
    on ``time.monotonic``; ``on_epoch(i, t)`` fires when the i-th ``train``
    region opens, which is where epoch i begins."""

    def __init__(self, on_epoch):
        self.spans = []
        self.epoch_starts = []
        self._open = {}
        self._on_epoch = on_epoch

    def start(self, name):
        t = time.monotonic()
        self._open[name] = t
        if name == "train":
            self.epoch_starts.append(t)
            self._on_epoch(len(self.epoch_starts) - 1, t)

    def stop(self, name):
        t0 = self._open.pop(name, None)
        if t0 is not None:
            self.spans.append((name, t0, time.monotonic()))

    def reset(self):
        pass


class TimedLoader:
    """The train loader with a stopwatch round ``next()``.  The trainer
    reaches through ``.loader`` for ``pad_specs`` / ``bucket_group`` exactly
    as it does through its own wrappers, so pipeline selection does not see
    it (test_driver_matches_run_training.py compares ``history["pipeline"]``
    with ``run_training``'s)."""

    def __init__(self, loader):
        self.loader = loader
        self.waits = []              # (t_end, seconds) per next()

    def set_epoch(self, epoch):
        self.loader.set_epoch(epoch)

    def __len__(self):
        return len(self.loader)

    def __iter__(self):
        it = iter(self.loader)
        while True:
            t0 = time.monotonic()
            try:
                batch = next(it)
            except StopIteration:
                return
            t1 = time.monotonic()
            self.waits.append((t1, t1 - t0))
            yield batch


@contextlib.contextmanager
def _env(name, value):
    prior = os.environ.get(name)
    os.environ[name] = value
    try:
        yield
    finally:
        if prior is None:
            os.environ.pop(name, None)
        else:
            os.environ[name] = prior


def _rel(a, b):
    return abs(a - b) / max(abs(b), 1e-12)


def assemble(config, samples, seed, loader_seed):
    """Everything ``_run_training_dict`` builds between data loading and
    the ``train_validate_test`` call.  Returns a dict of the pieces."""
    import jax

    from hydragnn_tpu.config.config import (
        DatasetStats, finalize, head_specs_from_config,
        label_slices_from_config, normalize_output_config)
    from hydragnn_tpu.data.dataloader import create_dataloaders
    from hydragnn_tpu.data.splitting import split_dataset
    from hydragnn_tpu.models.base import ModelConfig
    from hydragnn_tpu.models.create import create_model
    from hydragnn_tpu.parallel.zero import zero_stage_from_training
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.trainer import create_train_state

    training = config["NeuralNetwork"]["Training"]
    arch = config["NeuralNetwork"]["Architecture"]
    trainset, valset, testset = split_dataset(
        samples, training["perc_train"],
        config["Dataset"].get("compositional_stratified_splitting", False))
    stats = DatasetStats.from_samples(
        trainset + valset + testset, need_deg=arch["model_type"] == "PNA")
    config = normalize_output_config(finalize(config, stats))
    training = config["NeuralNetwork"]["Training"]
    head_specs = head_specs_from_config(config)
    gslices, nslices = label_slices_from_config(config)
    batch_size = int(training["batch_size"])
    n_local = len(jax.local_devices())
    if n_local > 1:
        batch_size = max(1, -(-batch_size // n_local))
    post_collate = None
    if arch["model_type"] == "DimeNet":
        from hydragnn_tpu.models.dimenet import (
            DnTriGate, add_dimenet_extras, count_triplets)

        max_per_sample = 1
        for s in trainset + valset + testset:
            if s.num_edges:
                max_per_sample = max(
                    max_per_sample, count_triplets(s.edge_index, s.num_nodes))
        max_triplets = -(-(batch_size * max_per_sample + 1) // 8) * 8
        tri_gate = DnTriGate(max_edges_per_graph=stats.max_edges)
        post_collate = lambda b: add_dimenet_extras(  # noqa: E731
            b, max_triplets, tri_gate=tri_gate)
    loaders = create_dataloaders(
        trainset, valset, testset, batch_size, head_specs,
        graph_feature_slices=gslices, node_feature_slices=nslices,
        seed=loader_seed, post_collate=post_collate)
    cfg = ModelConfig.from_config(config["NeuralNetwork"])
    model = create_model(cfg)
    opt_spec = select_optimizer(
        training["Optimizer"],
        zero_stage=zero_stage_from_training(training, env=False))
    state = create_train_state(model, next(iter(loaders[0])), opt_spec,
                               seed=seed)
    return {"config": config, "cfg": cfg, "model": model,
            "opt_spec": opt_spec, "state": state, "loaders": loaders,
            "n_train": len(trainset), "micro_batch": batch_size,
            "head_specs": head_specs, "slices": (gslices, nslices),
            "post_collate": post_collate, "trainset": trainset}


def step_parity(job, say):
    """``correct`` (a): one full train step (forward, backward, optimizer)
    on the cell's first micro-batch of train samples under the cell's
    backend against the same step under the composed ``scatter`` path —
    both under "highest" (TOL_F32) and both as shipped (TOL_DEFAULT).  The
    composed path is the program's own, not an independent reference."""
    import jax

    from hydragnn_tpu.data.dataloader import pad_spec_for
    from hydragnn_tpu.graph.batch import collate
    from hydragnn_tpu.train.trainer import make_train_step

    backend = os.environ.get(_BACKEND_ENV, "scatter")
    samples = job["trainset"][:job["micro_batch"]]
    spec = pad_spec_for(samples, len(samples))

    def batch_under(name):
        with _env(_BACKEND_ENV, name):
            b = collate(samples, spec, job["head_specs"], *job["slices"])
            if job["post_collate"] is not None:
                b = job["post_collate"](b)
        return jax.device_put(b)

    def one_step(name, batch, precision):
        ctx = (jax.default_matmul_precision(precision) if precision
               else contextlib.nullcontext())
        with _env(_BACKEND_ENV, name), ctx:
            step = jax.jit(make_train_step(
                job["model"], job["cfg"], job["opt_spec"],
                telemetry_metrics=True))
            _state, m = step(job["state"], batch)
            return float(m["loss"]), float(m["grad_norm"])

    b_cell, b_ref = batch_under(backend), batch_under("scatter")
    out = {"backend": backend}
    for label, precision, tol in (("highest", "highest", TOL_F32),
                                  ("as_shipped", None, TOL_DEFAULT)):
        loss, gn = one_step(backend, b_cell, precision)
        loss_r, gn_r = one_step("scatter", b_ref, precision)
        dev = max(_rel(loss, loss_r), _rel(gn, gn_r))
        out[label] = {"dev": dev, "tol": tol, "loss": loss, "ref_loss": loss_r}
        say(f"parity {label}: {backend} vs scatter rel dev {dev:.3e} "
            f"(tol {tol:g}; loss {loss:.6f} vs {loss_r:.6f})")
    out["ok"] = all(out[k]["dev"] <= out[k]["tol"]
                    for k in ("highest", "as_shipped"))
    return out


def median_epoch_rate(epochs):
    """``train_graphs_per_s``: the MEDIAN over the counted epochs of an
    epoch's train graphs / its wall seconds, not the window's sum.  The
    host's cores are shared, and one epoch boundary that meets a stalled
    host (1.8 % of a run, PERF.md, Findings) must not decide the run."""
    import numpy as np

    if not epochs:
        return None
    return float(np.median([e["graphs"] / (e["t1"] - e["t0"])
                            for e in epochs]))


def _read_events(logs_dir):
    events = []
    for path in glob.glob(os.path.join(logs_dir, "**", "events.jsonl"),
                          recursive=True):
        with open(path) as f:
            events += [json.loads(ln) for ln in f if ln.strip()]
    return events


class _Tracer:
    """One wall-clock profiler window, opened and closed by a timer thread
    (the program's step-scheduled Profiler counts dispatches, which on the
    non-blocking resident path are milliseconds apart)."""

    def __init__(self, out_dir, cap_s):
        self.out_dir = out_dir
        self.cap_s = cap_s
        self.window = None           # (t0, t1) on time.monotonic
        self.error = None
        self._timers = []

    def schedule(self, epoch_start, epoch_s):
        """Straddle the next epoch boundary: centre the window on it."""
        length = min(self.cap_s, 0.8 * epoch_s)
        begin = epoch_start + epoch_s - length / 2
        self._timers = [
            threading.Timer(max(0.0, begin - time.monotonic()), self._start),
            threading.Timer(max(0.0, begin + length - time.monotonic()),
                            self._stop)]
        for t in self._timers:
            t.daemon = True
            t.start()

    def _start(self):
        import jax

        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
            self.window = (time.monotonic(), None)
        except Exception as e:  # reported through the missing metrics
            self.error = repr(e)

    def _stop(self):
        import jax

        if self.window is None or self.window[1] is not None:
            return
        try:
            t1 = time.monotonic()
            jax.profiler.stop_trace()
            self.window = (self.window[0], t1)
        except Exception as e:
            self.error = repr(e)

    def finish(self):
        for t in self._timers:
            t.cancel()
        for t in self._timers:
            t.join()
        self._stop()


def run(ctx):
    t_begin = ctx["t_start"]
    say, config, seed = ctx["say"], ctx["config"], ctx["seed"]
    params = ctx["traffic"].get("driver_params", {})

    import jax

    from hydragnn_tpu.utils.runtime import setup_compile_cache

    cache_dir = setup_compile_cache()
    # every program goes to the cache, the small eagerly dispatched ones of
    # create_train_state and _run_epoch too (JAX keeps only compilations
    # of a second and more by default: they were compiled anew in every
    # run, 10-60 s of set-up)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    say(f"compile cache: {cache_dir}")
    compiles = []                    # (t, event) of every program built
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, _secs, **_kw: compiles.append((time.monotonic(), event))
        if event.endswith(("backend_compile_duration",
                           "cache_retrieval_time_sec")) else None)

    # -- data ---------------------------------------------------------------
    t0 = time.monotonic()
    corpus_cfg = config["corpus"]
    samples = ctx["corpus"](corpus_cfg, seed, config)
    say(f"corpus: {corpus_cfg['generator']} n={len(samples)} "
        f"seed={seed} in {time.monotonic() - t0:.1f}s")

    # run_training's scoped export of Architecture.aggregation_backend
    backend = config["NeuralNetwork"]["Architecture"].get(
        "aggregation_backend")
    exported = bool(backend) and _BACKEND_ENV not in os.environ
    if exported:
        os.environ[_BACKEND_ENV] = str(backend)
    try:
        return _run(ctx, samples, params, compiles, t_begin)
    finally:
        if exported:
            os.environ.pop(_BACKEND_ENV, None)


def _run(ctx, samples, params, compiles, t_begin):
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
    job = assemble(config, samples, seed,
                   seed if loader_seed is None else loader_seed)
    config = job["config"]
    say(f"assembled in {time.monotonic() - t0:.1f}s: n_train={job['n_train']}"
        f" micro_batch={job['micro_batch']} "
        f"pad_specs={[(p.num_nodes, p.num_edges) for p in job['loaders'][0].pad_specs]}")
    checks = {}
    # the trainer donates its state; the parity step, which runs AFTER the
    # job so that its four programs neither count as set-up nor reserve
    # device memory while the trainer's peak is taken, gets a copy
    state0 = jax.tree.map(lambda a: a.copy(), job["state"])

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

    tracer = (_Tracer(os.path.join(ctx["workdir"], "trace"), TRACE_CAP_S)
              if ctx["trace"] else None)

    def on_epoch(i, t):
        if i >= 2 and t > clock.epoch_starts[1] + seconds:
            raise WindowClosed
        if i == 2 and tracer is not None:
            tracer.schedule(t, t - clock.epoch_starts[1])

    clock = RegionClock(on_epoch)
    tr.initialize(timer=True, jax_annotations=bool(ctx["trace"]))
    tr._tracers["bench"] = clock     # no public register(): PERF.md, open
    train_l, val_l, test_l = job["loaders"]
    timed = TimedLoader(train_l)
    try:
        train_validate_test(
            job["model"], job["cfg"], job["state"], job["opt_spec"],
            timed, val_l, test_l, config["NeuralNetwork"], log_name,
            config.get("Verbosity", {}).get("level", 0),
            rank=0, world_size=1, logs_dir=logs_dir,
            profile_config=config.get("Profile"), telemetry=telemetry)
    except WindowClosed:
        pass
    finally:
        if tracer is not None:
            tracer.finish()
        tr.initialize()
    t_done = time.monotonic()
    # HBM held at the fullest: the allocator keeps live buffers
    # (peak_bytes_in_use) apart from what loaded programs reserve for
    # their temporaries (peak_bytes_reserved); free = limit - both, and the
    # two peaks need not coincide, so the larger one is a LOWER bound of
    # the true peak and their sum an upper bound
    stats = [d.memory_stats() or {} for d in jax.local_devices()]
    live = max((m.get("peak_bytes_in_use") or 0) for m in stats)
    peak = max(max(m.get("peak_bytes_in_use") or 0,
                   m.get("peak_bytes_reserved") or 0) for m in stats)
    say(f"memory_stats[0]: {json.dumps(stats[0])}")
    t0 = time.monotonic()
    job["state"] = state0
    checks["parity"] = step_parity(job, say)
    say(f"parity in {time.monotonic() - t0:.1f}s")

    # -- what was counted ---------------------------------------------------
    starts = clock.epoch_starts
    if len(starts) < 2:
        raise RuntimeError("the job ended before epoch 1 began")
    t_open = starts[1]
    t_close = t_open + seconds
    counted = [e for e in range(1, len(starts) - 1)
               if starts[e + 1] <= t_close]
    events = _read_events(logs_dir)
    manifest = next((e for e in events if e.get("event") == "manifest"), {})
    # what train_validate_test would have returned: the trainer's teardown
    # writes it into the manifest on every exit path
    history = manifest["history"]
    steps_by_epoch = {}
    for ev in events:
        if ev.get("event") == "step":
            steps_by_epoch.setdefault(ev["epoch"], []).append(ev)
    epochs = []
    for e in counted:
        recs = steps_by_epoch.get(e, [])
        epochs.append({
            "epoch": e, "t0": starts[e], "t1": starts[e + 1],
            "graphs": sum(r["num_graphs"] for r in recs),
            "steps": sum(r["steps_in_dispatch"] for r in recs),
            "skipped": sum(r.get("skipped", 0) for r in recs),
            "nonfinite": sum(1 for r in recs
                             if not np.isfinite(r["loss"])),
            "edges_real": sum(r["padding"]["edges_real"] for r in recs),
            "edges_padded": sum(r["padding"]["padded_edges"] for r in recs),
        })
    wall = (epochs[-1]["t1"] - t_open) if epochs else 0.0
    graphs = sum(e["graphs"] for e in epochs)
    rate = median_epoch_rate(epochs)
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
    # a dry run rehearses the control flow, not the window's length
    min_epochs = 1 if ctx["dry"] else MIN_EPOCHS
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
    dispatch = manifest.get("aggr_dispatch") or {}
    fallbacks = [e for e in events if e.get("event") == "health"
                 and e.get("kind") in ("fused_fallback", "egcl_fallback")]
    want_ops = expect.get("fused_ops", [])
    checks["dispatch"] = {
        "aggr_dispatch": dispatch, "fused_fallback_events": len(fallbacks),
        "ok": not fallbacks and all(
            dispatch.get(f"{op}:fused", 0) > 0
            and f"{op}:scatter" not in dispatch for op in want_ops)}
    in_window = [ev for t, ev in compiles if t_open <= t <= t_close]
    checks["compiles_in_window"] = {"got": len(in_window),
                                    "events": in_window[:4],
                                    "ok": not in_window}
    for name, c in checks.items():
        if not c["ok"]:
            say(f"CHECK FAILED {name}: {json.dumps(c, default=str)}")
    correct = all(c["ok"] for c in checks.values())

    # HBM held at the fullest: the allocator keeps live buffers
    # (peak_bytes_in_use) apart from what loaded programs reserve for
    # their temporaries (peak_bytes_reserved); free = limit - both, and the
    # two peaks need not coincide, so the larger one is a LOWER bound of
    # the true peak and their sum an upper bound
    facts = {
        "epochs": epochs, "pipeline": pipe, "history": losses,
        "spans": clock.spans,
        "loader_waits": timed.waits,
        "memory_peak_bytes": peak or None,
        "memory_live_peak_bytes": live or None,
        "trace_window": tracer.window if tracer else None,
        "trace_dir": tracer.out_dir if tracer else None,
        "trace_error": tracer.error if tracer else None,
        "mono_to_unix_ns": time.time_ns() - time.monotonic() * 1e9,
        "train_module_regex": TRAIN_MODULES,
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
