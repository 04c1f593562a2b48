"""Benchmark: training throughput + honest roofline of the flagship config.

Prints ONE COMPACT JSON line (<1 KB): {"metric", "value", "unit",
"vs_baseline", "mfu_pct", "dense", "archs", ...}; the full evidence
blocks (rooflines, methods, epoch times, knobs) go to
``BENCH_evidence.json`` next to this file.  Round-4 post-mortem drove
this split: the r03 cumulative line (~4 KB) overflowed the driver's tail
window (``parsed: null`` at rc=0), and r04's grown phase list blew the
driver's wall-clock budget (rc=124) while the parent BUFFERED the
child's stdout — so an outer SIGKILL lost every phase the child had
already finished.  Three fixes, in this file:

  1. STREAM, don't buffer: the parent tees each child line to its own
     stdout the moment it arrives, so the driver's tail always holds the
     last finished measurement even if the parent itself is SIGKILLed.
  2. COMPACT final line: headline + MFU + per-rung/per-arch numbers
     only; everything else in BENCH_evidence.json.
  3. DEADLINE-AWARE phases: the parent passes an absolute deadline down
     (HYDRAGNN_BENCH_DEADLINE); the child checks a per-unit wall-clock
     estimate before starting each expensive unit and records what it
     skipped, so rc=0 + a parseable line survive ANY outer budget.

The child keeps JAX's persistent compilation cache where
``JAX_COMPILATION_CACHE_DIR`` says, else at ``<checkout>/.jax_cache``
(hydragnn_tpu/utils/runtime.py:setup_compile_cache), so the dominant
per-phase cost (20-40 s XLA compiles) is a cache hit on every run after
the first.

Evidence blocks (round-3 VERDICT items 1/2/5):

  value                  chip-loop ceiling, graphs/sec/chip (headline; same
                         definition as rounds 1-2 for comparability)
  sustained              what a ``run_training`` user gets end-to-end:
                         loader -> stack -> resident replay -> scanned step,
                         measured through the real trainer epoch loop
  sustained_default      the same loop with NO env knobs: _auto_pipeline
                         picks scan/residency, val/test epochs run — the
                         true out-of-the-box number (round-4 item 7)
  roofline               measured-method roofline for the SAME program that
                         is timed: flops from XLA's cost model (fusion-
                         invariant), bytes from XLA's buffer assignment
                         (memory_analysis: args + outputs + 2*temps; see
                         _roofline for why the cost model and naive HLO
                         sums both overcount), achieved HBM GB/s, MFU
                         against the MXU's native 197 TF/s bf16 peak
                         (JAX's default matmul precision runs f32 dots
                         through the MXU as bf16 — measured 56.7 TF/s on
                         an 8192^3 f32 matmul here, >49 TF/s "f32 peak",
                         so 49e12 is the wrong basis; r02 used it)
  membw_probe            measured achievable HBM bandwidth on THIS chip
                         (streamed x*a copy, 2 sizes) — the denominator any
                         bandwidth-bound claim has to live under
  dense                  compute-dense flagship (hidden-256 SchNet, bf16):
                         same measurements where MFU is a meaningful axis
  archs                  per-arch sweep: all 9 stacks, chip-loop throughput

The reference publishes no throughput numbers (BASELINE.md), so
``vs_baseline`` is the ratio against BASELINE.json["published"] when
present, else 1.0.

Process model: measurement runs in ONE child process under a hard
timeout; the parent never imports JAX (a parent that has touched JAX holds
the chip, and the child would fail or hang).  The child re-prints the
cumulative headline line after EVERY phase and the parent tees it
through, so a timeout mid-phase leaves the finished phases on stdout.
The chip is never hidden: a child that finds no TPU exits non-zero before
printing any metric, a failed phase raises (non-zero exit), and the parent
exits non-zero whenever the child did or printed no headline value.  The
only CPU mode is the explicit HYDRAGNN_BENCH_PLATFORM=cpu, whose every
line says ``"platform": "cpu"`` — counts and control flow, never a rate to
quote.

Env knobs: HYDRAGNN_BENCH_PLATFORM=tpu|cpu (default tpu),
HYDRAGNN_BENCH_TIMEOUT (child wall-clock seconds, default 1380 — sized
to sit under a ~30 min outer kill with headroom), HYDRAGNN_BENCH_PHASES
(comma list of ceiling,roofline,sustained_default,sustained,dense,archs;
default all-but-`sustained` on TPU — the knobbed sustained variant
duplicates sustained_default's path and is opt-in — ceiling-only on
CPU), HYDRAGNN_BENCH_DTYPE (flagship compute dtype, default float32).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

METRIC = "qm9_schnet_train_throughput"
UNIT = "graphs/sec/chip"


def _mxu_peak() -> float:
    """MFU peak basis: the published bf16 peak of THIS process's device
    (also the right basis for default-precision f32 — see module
    docstring), from the ONE table shared with the in-run telemetry
    (hydragnn_tpu/telemetry/flops.py:DEVICE_PEAKS) so bench and telemetry
    MFU cannot drift.  A device outside the table is an error — an MFU
    against another part's roofline is not a measurement.  Imported lazily:
    the parent process must not import the package (it pulls jax)."""
    from hydragnn_tpu.telemetry.flops import require_peak_flops

    return require_peak_flops()

# the per-arch sweep list is hydragnn_tpu.models.create.ALL_ARCHS — the ONE
# canonical list shared with the parity tests — imported lazily inside the
# child (the parent process must not import the package before choosing a
# platform)


def _baseline_ratio(graphs_per_sec: float) -> float:
    published = {}
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(here, "BASELINE.json")) as f:
            published = json.load(f).get("published", {}) or {}
    except Exception:
        pass
    base = published.get("graphs_per_sec_per_chip")
    return (graphs_per_sec / float(base)) if base else 1.0


# ---------------------------------------------------------------------------
# child-side measurement helpers
# ---------------------------------------------------------------------------


def _sync(tree):
    """Completion barrier by host fetch: a device->host transfer of one
    (small) leaf waits for the program that produced it on any runtime,
    and consumes the result inside the timed region."""
    import jax
    import numpy as np

    np.asarray(jax.tree_util.tree_leaves(tree)[0])


# the ONE optimizer every bench mode trains with: _zero_main's dp steps
# must run the exact hyperparameters _build initialized the opt state under
BENCH_OPTIMIZER = {"type": "AdamW", "learning_rate": 1e-3}


def _build(model_type="SchNet", hidden=64, dtype="float32", batch_size=512,
           nodes_per_graph=20, tight_edges=False, trace_only=False):
    """Flagship-shaped synthetic setup for one arch: QM9-scale graphs
    (~20 atoms), radius graph, single graph head.

    ``tight_edges`` pads the edge array to the batch's REAL edge total
    (rounded up) instead of batch * per-graph-max — the layout a bucketed
    loader achieves (~1.05x real vs ~2x).  Used by the dense phase both
    to measure the deployment-realistic rung and to compute the
    honest useful-flops basis (a composed twin at loose padding spends
    flops on padding edges that no ideal implementation needs)."""
    import jax
    import numpy as np

    from hydragnn_tpu.graph.batch import (
        GraphSample, HeadSpec, PadSpec, collate)
    from hydragnn_tpu.graph.neighborlist import radius_graph
    from hydragnn_tpu.models.base import GraphHeadCfg, ModelConfig
    from hydragnn_tpu.models.create import create_model
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.trainer import create_train_state, make_train_step

    # CGConv preserves feature dim, so CGCNN's width IS the input width
    in_dim = hidden if model_type == "CGCNN" else 1
    rng = np.random.RandomState(0)
    samples = []
    for _ in range(batch_size):
        n = nodes_per_graph
        pos = rng.rand(n, 3).astype(np.float32) * 4.0
        x = (rng.rand(n, in_dim).astype(np.float32) if in_dim > 1
             else rng.randint(0, 5, (n, 1)).astype(np.float32))
        ei = radius_graph(pos, radius=1.8, max_neighbours=20)
        samples.append(GraphSample(
            x=x, pos=pos, edge_index=ei,
            graph_y=rng.rand(1).astype(np.float32), node_y=x[:, :1]))
    heads = [HeadSpec("energy", "graph", 1)]
    pad = PadSpec.for_batch(batch_size, nodes_per_graph,
                            max(s.num_edges for s in samples))
    if tight_edges:
        import dataclasses

        tot = sum(s.num_edges for s in samples)
        pad = dataclasses.replace(
            pad, num_edges=-(-(tot + 1) // 256) * 256)
    batch = collate(samples, pad, heads)
    if model_type == "DimeNet":
        from hydragnn_tpu.models.dimenet import (
            add_dimenet_extras, count_triplets)
        import numpy as np2

        real = np2.asarray(batch.edge_mask) > 0
        ei_real = np2.stack([np2.asarray(batch.senders)[real],
                             np2.asarray(batch.receivers)[real]])
        t = count_triplets(ei_real, batch.x.shape[0])
        batch = add_dimenet_extras(batch, max_triplets=t + 8)

    cfg = ModelConfig(
        model_type=model_type,
        input_dim=in_dim,
        hidden_dim=hidden,
        output_dim=(1,),
        output_type=("graph",),
        graph_head=GraphHeadCfg(2, hidden, 2, (hidden, hidden)),
        node_head=None,
        task_weights=(1.0,),
        num_conv_layers=4,
        num_gaussians=50,
        num_filters=hidden,
        radius=1.8,
        max_neighbours=20,
        max_degree=20,
        pna_avg_deg_log=1.8,
        pna_avg_deg_lin=6.0,
        envelope_exponent=5,
        num_before_skip=1,
        num_after_skip=2,
        num_radial=6,
        num_spherical=7,
        basis_emb_size=8,
        int_emb_size=64,
        out_emb_size=64,
        # validated by ModelConfig.__post_init__ — a typo raises rather
        # than silently benchmarking f32 while claiming bf16
        compute_dtype=dtype,
    )
    model = create_model(cfg)
    if trace_only:
        # abstract init only: the model's Python runs (so the trace-time
        # dispatch tally fires and the fused/scatter branch is decided)
        # but nothing executes — on CPU the fused kernels would run in
        # Pallas interpret mode, minutes per step
        jax.eval_shape(
            lambda b: model.init(
                {"params": jax.random.PRNGKey(0),
                 "dropout": jax.random.PRNGKey(1)}, b, train=False),
            batch)
        return None, batch, None, cfg, samples, heads
    opt_spec = select_optimizer(BENCH_OPTIMIZER)
    state = create_train_state(model, batch, opt_spec)
    batch = jax.device_put(batch)
    step = make_train_step(model, cfg, opt_spec)
    return state, batch, step, cfg, samples, heads


def _release_device():
    """Free ALL device buffers and compiled executables between phases.

    Each _chip_loop compile closes over its batch, so the jit cache pins
    every phase's batch/state on the chip for the child's whole lifetime —
    on the 16 GB v5e the multi-phase run RESOURCE_EXHAUSTs by the dense
    h1024 build unless earlier phases' buffers are actively dropped
    (clear_caches releases the executables, delete() the arrays).  Callers
    must be at a phase boundary: every live array is invalidated."""
    import gc

    import jax

    jax.clear_caches()
    gc.collect()
    try:
        for a in jax.live_arrays():
            a.delete()
    except Exception:  # noqa: BLE001 — best-effort on exotic runtimes
        pass


def _chip_loop(state, batch, step, n_iters, n_repeats):
    """Best-of-N timing of K steps inside one compiled fori_loop (per-step
    host dispatch otherwise dominates; the train state threads through the
    carry so nothing is hoisted or DCE'd)."""
    import jax
    from jax import lax

    @jax.jit
    def run_k(state0):
        def body(_, s):
            s, _m = step(s, batch)
            return s
        return lax.fori_loop(0, n_iters, body, state0)

    state = run_k(state)  # compile + warmup
    _sync(state.params)
    best = float("inf")
    for _ in range(n_repeats):
        t0 = time.perf_counter()
        state = run_k(state)
        _sync(state.params)
        best = min(best, time.perf_counter() - t0)
    return best / n_iters, state


def _roofline(step, state, batch, step_s):
    """Roofline fields for the SAME per-step program being timed.

    flops: XLA cost model (fusion-invariant, reliable).
    bytes: XLA's buffer assignment (``compiled.memory_analysis()``) — the
    r02 cost-model bytes were fusion-blind and implied 1.9x the v5e's HBM
    spec (VERDICT weak-1), and naive HLO-boundary sums overcount shared
    operands/async DMA bookkeeping.  The buffer-assignment estimate is
    structural: program arguments are read, outputs are written, and every
    HBM temp buffer is written once and read at least once, so

        bytes/step ~ argument_size + output_size + 2 * temp_size

    This slightly UNDERcounts (a temp re-read by several kernels is billed
    once) and is therefore a defensible achieved-bandwidth figure — on the
    v5e it lands well below both the 819 GB/s HBM spec and the measured
    probe bandwidth, unlike its predecessors.
    """
    import jax

    compiled = jax.jit(step).lower(state, batch).compile()
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    cm_bytes = float(ca.get("bytes accessed", 0.0))
    ma = compiled.memory_analysis()
    ba_bytes = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                + 2 * ma.temp_size_in_bytes)
    out = {
        "flops_per_step": round(flops),
        "achieved_tflops": round(flops / step_s / 1e12, 3),
        "mfu_pct": round(flops / step_s / _mxu_peak() * 100, 2),
        "mfu_peak_basis_tflops": round(_mxu_peak() / 1e12),
        "hbm_bytes_per_step": int(ba_bytes),
        "hbm_gbps": round(ba_bytes / step_s / 1e9, 1),
        "bytes_method": "XLA buffer assignment: args + outputs + 2*temps "
                        "(each HBM temp written once + read >= once); the "
                        "fusion-blind cost-model figure is reported only "
                        "as cost_model_bytes_per_step",
        "temp_bytes": int(ma.temp_size_in_bytes),
        "cost_model_bytes_per_step": int(cm_bytes),
    }
    return out


def _cost_flops(step, state, batch):
    """XLA cost-model flops of one compiled step — the SHARED flops-basis
    helper (telemetry/flops.py), so the in-run telemetry MFU estimate and
    this bench's figures can never drift apart."""
    from hydragnn_tpu.telemetry.flops import step_cost_flops

    return step_cost_flops(step, state, batch)


def _membw_probe():
    """Measured achievable HBM bandwidth, overhead-cancelled: time a
    streamed y = x*a at two working-set sizes and take the MARGINAL
    bandwidth (delta traffic / delta time), which cancels the fixed
    per-kernel/per-iteration overheads that dominate small arrays —
    exactly the regime a 512-graph GNN step lives in, which is why the
    raw small-size number is also reported."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def timed(mb):
        n_rows = mb * 1024 * 1024 // (4 * 1024)
        x = jnp.ones((n_rows, 1024), jnp.float32)

        @jax.jit
        def probe(x, s):
            def body(_, c):
                x, s = c
                y = x * 1.0000001
                return y, s + y[0, 0] * 1e-30
            return lax.fori_loop(0, 8, body, (x, s))

        y, s = probe(x, jnp.float32(1e-9))
        _sync(s)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            y, s = probe(x, jnp.float32(1e-9))
            _sync(s)
            best = min(best, time.perf_counter() - t0)
        return best, 8 * 2 * mb * 1024 * 1024

    t_small, b_small = timed(64)
    t_big, b_big = timed(2048)
    t_mid, b_mid = timed(1024)
    out = {
        "raw_64MB_gbps": round(b_small / t_small / 1e9, 1),
        "raw_2GB_gbps": round(b_big / t_big / 1e9, 1),
        "method": "jit fori_loop of y = x*a (read+write), best of 3, "
                  "completion forced by host fetch; marginal = "
                  "(bytes_2GB - bytes_1GB)/(t_2GB - t_1GB), cancelling "
                  "fixed per-kernel overheads",
    }
    if t_big > t_mid:
        out["marginal_gbps"] = round((b_big - b_mid) / (t_big - t_mid) / 1e9,
                                     1)
    else:
        # timing inversion (host stall mid-probe): the marginal figure
        # would be nonsense — flag it and let the raw number stand
        out["marginal_gbps_error"] = "timing inversion between sizes"
    return out


def _sustained(samples, heads, default_path=False):
    """What a run_training user gets: the real trainer epoch loop (loader ->
    DeviceStackLoader -> ResidentDeviceLoader -> scanned jit step), measured
    over full epochs after a warmup epoch that pays compile + staging.

    ``default_path=True`` measures the OUT-OF-THE-BOX configuration: no env
    knobs at all — scan chunking/residency are whatever _auto_pipeline
    selects, and val/test epochs run (the round-4 default-path headline).
    """
    import jax
    import numpy as np

    from hydragnn_tpu.data.dataloader import create_dataloaders
    from hydragnn_tpu.models.base import GraphHeadCfg, ModelConfig
    from hydragnn_tpu.models.create import create_model
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.trainer import (
        create_train_state, train_validate_test)

    knob_keys = ("HYDRAGNN_VALTEST", "HYDRAGNN_STEPS_PER_DISPATCH",
                 "HYDRAGNN_RESIDENT_DATASET")
    saved_env = {k: os.environ.get(k) for k in knob_keys}
    if default_path:
        for k in knob_keys:
            os.environ.pop(k, None)
    else:
        os.environ["HYDRAGNN_VALTEST"] = "0"
        # scan-32: with 8 steps per dispatch the per-dispatch host latency
        # left a 31% gap to the chip ceiling in rounds 1-5 (docs/PERF.md);
        # 32 amortizes it 4x
        os.environ.setdefault("HYDRAGNN_STEPS_PER_DISPATCH", "32")
        os.environ.setdefault("HYDRAGNN_RESIDENT_DATASET", "1")

    n_batches = 64
    batch_size = 512
    # deterministic corpus: the flagship samples cycled to 64 batches
    big = [samples[i % len(samples)] for i in range(n_batches * batch_size)]
    train_loader, val_loader, test_loader = create_dataloaders(
        big, big[:batch_size], big[:batch_size], batch_size, heads)

    cfg = ModelConfig(
        model_type="SchNet", input_dim=1, hidden_dim=64, output_dim=(1,),
        output_type=("graph",), graph_head=GraphHeadCfg(2, 64, 2, (64, 64)),
        node_head=None, task_weights=(1.0,), num_conv_layers=4,
        num_gaussians=50, num_filters=64, radius=1.8, max_neighbours=20,
        compute_dtype=os.getenv("HYDRAGNN_BENCH_DTYPE", "float32").strip())
    model = create_model(cfg)
    opt_spec = select_optimizer(BENCH_OPTIMIZER)
    state = create_train_state(model, next(iter(train_loader)), opt_spec)

    n_epochs = 6
    config_nn = {
        "Training": {"num_epoch": n_epochs},
        "Variables_of_interest": {"output_names": ["energy"]},
    }
    # ONE call: epoch 0 pays trace+compile and the one-time resident
    # staging; the trainer records per-epoch wall time in
    # history["epoch_time"], so the steady-state epochs are separable
    # without re-running (a second call would re-trace and re-stage,
    # measuring harness artifacts instead of training)
    try:
        state, history = train_validate_test(
            model, cfg, state, opt_spec, train_loader, val_loader,
            test_loader, config_nn, "bench_sustained", verbosity=0, rank=0,
            world_size=1)
        _sync(state.params)
        # drop_last stacking: graphs actually consumed per epoch
        if default_path:
            # EXACT provenance: the trainer records the configuration it
            # actually ran with (re-deriving via _auto_pipeline afterwards
            # can disagree near the residency budget boundary)
            pipe = history.get("pipeline", {})
            spd = int(pipe.get("steps_per_dispatch", 1))
            resident = bool(pipe.get("resident", False))
            valtest = 1
        else:
            spd = int(os.environ.get("HYDRAGNN_STEPS_PER_DISPATCH", "1"))
            resident = int(
                os.environ.get("HYDRAGNN_RESIDENT_DATASET", "0") or 0)
            valtest = int(os.environ.get("HYDRAGNN_VALTEST", "1") or 0)
    finally:
        # restore the caller's knobs even when training raises — a leaked
        # pop/setdefault would silently change every later bench phase
        for k, v in saved_env.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    n_used = (n_batches // spd) * spd * batch_size
    steady = sorted(history["epoch_time"][2:])
    med = steady[len(steady) // 2]
    return {
        "graphs_per_sec": round(n_used / med, 1),
        "epoch_time_s": [round(t, 3) for t in history["epoch_time"]],
        "graphs_per_epoch": n_used,
        "knobs": {  # ACTUAL configuration at measurement time (for the
                    # default path: what _auto_pipeline selected)
            "HYDRAGNN_STEPS_PER_DISPATCH": spd,
            "HYDRAGNN_RESIDENT_DATASET": int(bool(resident)),
            "HYDRAGNN_VALTEST": valtest,
            "auto_selected": bool(default_path),
        },
        "method": "median steady-state epoch wall time (epochs 2+; epoch 0 "
                  "pays compile + one-time device staging) of the real "
                  "train_validate_test loop — includes scheduler/history/"
                  "host overheads a real run pays",
    }


# ---------------------------------------------------------------------------
# child
# ---------------------------------------------------------------------------

_EVIDENCE_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "BENCH_evidence.json")

# conservative per-unit wall-clock estimates (s) for the deadline guard —
# COLD-compile numbers; with the persistent compile cache warm the real
# costs are several times smaller, so the guard only bites when the cache
# is cold AND the outer budget is tight, which is exactly when skipping
# the tail phases is the right call.
# measured costs (cold / warm-cache): the DimeNet programs' Pallas-heavy
# modules are NOT covered by the persistent cache on this runtime
# (~310 s every run) — their estimates stay at the cold figure
_EST = {
    "roofline": 60, "dense_256": 100, "dense_512": 150, "dense_1024": 340,
    "arch": 40, "arch_gat": 80, "arch_dimenet": 330, "arch_dimenet_bf16": 150,
    "sustained_default": 180, "sustained": 160,
}


def _arch_est(arch: str) -> float:
    if arch.startswith("DimeNet-bf16"):
        return _EST["arch_dimenet_bf16"]
    if arch.startswith("DimeNet"):
        return _EST["arch_dimenet"]
    if arch.startswith("GAT"):
        return _EST["arch_gat"]
    return _EST["arch"]


def _dispatch_backend(before: dict, after: dict) -> str:
    """The aggregation backend an arch ACTUALLY used, from the trace-time
    dispatch tally delta around its build+measure (telemetry/pipeline.py):
    'fused' / 'scatter' / 'mixed(...)' / 'none'.  This is how a config
    that silently fell off the fast path shows up in the arch records."""
    from hydragnn_tpu.telemetry import pipeline

    return pipeline.dispatch_summary(pipeline.dispatch_delta(before, after))


def _deadline_remaining() -> float:
    d = float(os.getenv("HYDRAGNN_BENCH_DEADLINE", "0") or 0.0)
    return (d - time.time()) if d > 0 else float("inf")


def _shrunk(compact: dict) -> str:
    """Serialize the compact line, enforcing the <1 KB driver-tail contract
    by dropping optional blocks in reverse-importance order if needed."""
    line = json.dumps(compact, separators=(",", ":"))
    for drop in ("fused_archs", "aggr_fallback", "skipped", "sustained_gps",
                 "dense", "archs"):
        if len(line) <= 1000:
            break
        compact = {k: v for k, v in compact.items() if k != drop}
        line = json.dumps(compact, separators=(",", ":"))
    return line


def _child(platform: str) -> None:
    """Run the measurement phases under the parent-supplied deadline,
    printing the cumulative COMPACT line after every finished unit (the
    parent tees it straight through, so a kill at any point leaves the
    most complete measurement as the last stdout line) and mirroring the
    full evidence to BENCH_evidence.json."""
    # flagship tuning: the fused message-passing kernel (ops/fused_mp.py) is
    # exact (tests/test_fused_block.py) and measured +26% end-to-end at these
    # shapes (61.0k -> 76.6k graphs/s dense-schedule; docs/PERF.md).  In the
    # explicit CPU mode the fused kernels would run in Pallas INTERPRET mode
    # — minutes per step — so the composed XLA path (what a CPU user gets)
    # stays the backend there.
    if platform != "cpu":
        os.environ.setdefault("HYDRAGNN_AGGR_BACKEND", "fused")

    import jax

    from hydragnn_tpu.utils.runtime import setup_compile_cache

    cache_dir = setup_compile_cache()

    if platform == "cpu":
        jax.config.update("jax_platforms", "cpu")

    devs = jax.devices()
    on_tpu = devs[0].platform == "tpu"
    print(f"bench: platform={devs[0].platform} "
          f"device_kind={devs[0].device_kind} devices={len(devs)} "
          f"compile_cache={cache_dir} "
          f"deadline_in={_deadline_remaining():.0f}s", file=sys.stderr)
    if devs[0].platform != platform:
        # the chip is never hidden: no metric line has been printed yet
        sys.exit(f"bench: asked for platform {platform!r} but JAX reports "
                 f"{devs[0].platform!r} — refusing to measure (the only "
                 "CPU mode is the explicit HYDRAGNN_BENCH_PLATFORM=cpu)")

    # `sustained` (the hand-knobbed variant) is opt-in: sustained_default
    # measures the same trainer path as _auto_pipeline actually ships it
    default_phases = (
        "ceiling,roofline,sustained_default,dense,archs"
        if on_tpu else "ceiling")
    phases = [p.strip() for p in os.getenv(
        "HYDRAGNN_BENCH_PHASES", default_phases).split(",") if p.strip()]
    dtype = os.getenv("HYDRAGNN_BENCH_DTYPE", "float32").strip()
    n_iters = 200 if on_tpu else 5
    n_repeats = 3 if on_tpu else 1

    # compact: what the driver's tail window parses (<1 KB).
    # evidence: the full record, mirrored to BENCH_evidence.json.
    # the explicit CPU mode must never overwrite the chip's evidence record
    evidence_path = _EVIDENCE_PATH if on_tpu else _EVIDENCE_PATH.replace(
        ".json", ".cpu.json")
    compact = {"metric": METRIC, "value": 0.0, "unit": UNIT,
               "vs_baseline": 0.0, "platform": devs[0].platform,
               "evidence": os.path.basename(evidence_path)}
    evidence = {"metric": METRIC, "value": 0.0, "unit": UNIT,
                "vs_baseline": 0.0, "platform": devs[0].platform}
    skipped = []

    def emit():
        if skipped:
            compact["skipped"] = skipped
            evidence["skipped"] = skipped
        try:
            tmp = evidence_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(evidence, f, indent=1)
            os.replace(tmp, evidence_path)
        except Exception as e:  # noqa: BLE001 — never fail the line for it
            print(f"bench: evidence write failed: {e!r}", file=sys.stderr)
        print(_shrunk(dict(compact)), flush=True)

    def want(phase, est):
        if phase not in phases:
            return False
        if _deadline_remaining() < est:
            skipped.append(phase)
            print(f"bench: skipping {phase} (needs ~{est}s, "
                  f"{_deadline_remaining():.0f}s left)", file=sys.stderr)
            return False
        return True

    # --- ceiling (headline) ---
    t_c = time.perf_counter()
    state, batch, step, cfg, samples, heads = _build(dtype=dtype)
    step_s, state = _chip_loop(state, batch, step, n_iters, n_repeats)
    print(f"bench: flagship compile+measure "
          f"{time.perf_counter() - t_c:.1f}s", file=sys.stderr)
    gps = 512 / step_s
    for d in (compact, evidence):
        d["value"] = round(gps, 2)
        # an explicit-CPU run must not be ratioed against the TPU baseline
        d["vs_baseline"] = round(_baseline_ratio(gps) if on_tpu else 1.0, 4)
        d["step_ms"] = round(step_s * 1e3, 3)
    emit()

    if want("roofline", _EST["roofline"]):
        rf = _roofline(step, state, batch, step_s)
        evidence["roofline"] = rf
        evidence["membw_probe_gbps"] = _membw_probe()
        compact["roofline"] = {
            "mfu_pct": rf["mfu_pct"], "hbm_gbps": rf["hbm_gbps"]}
        emit()
    # flagship state/batch/step are dead past roofline — drop them (and the
    # executables pinning them) before the trainer-based phases.  NOTE
    # (_release_device contract): no device array may be held across this
    # call; `samples`/`heads` used below are host-side numpy.
    _release_device()

    if "dense" in phases:
        # compute-dense flagship ladder: MFU scales with width.  Rungs:
        # three loose-padding points (round-over-round comparable with the
        # r03/r04 ladder) plus a TIGHT-padding h1024 rung — the edge array
        # padded to the real edge total, i.e. what a bucketed loader ships
        # (graph/batch.py pads to batch x per-graph-max = ~2x real edges
        # at QM9 shapes; the fused kernels schedule-skip the padding but
        # the composed ops and HBM streams outside the kernels cannot).
        # MFU accounting: the useful-flops basis is ALWAYS the composed
        # twin at TIGHT padding — padding-edge flops are not useful work,
        # so a loose twin would inflate the fused rungs' MFU now that the
        # kernels skip that work.  The loose-twin figure is kept as
        # mfu_pct_loose_twin for r04 comparability.
        dense = {}
        dense_c = {}
        # tight-twin flops cache: the loose and tight rungs at the same
        # (hidden, batch) share one twin program — one compile, not two
        twin_flops = {}
        for hidden, dense_batch, tight in (
                (256, 512, False), (512, 512, False),
                (1024, 2048, False), (1024, 2048, True)):
            est = _EST[f"dense_{hidden}"]
            if _deadline_remaining() < est:
                skipped.append(f"dense_{hidden}{'t' if tight else ''}")
                print(f"bench: skipping dense h{hidden} (needs ~{est}s, "
                      f"{_deadline_remaining():.0f}s left)", file=sys.stderr)
                continue
            t0 = time.perf_counter()
            dstate, dbatch, dstep, dcfg, _s, _h = _build(
                hidden=hidden, dtype="bfloat16", batch_size=dense_batch,
                tight_edges=tight)
            dstep_s, dstate = _chip_loop(
                dstate, dbatch, dstep,
                max(n_iters // (8 if hidden < 1024 else 40), 2),
                n_repeats)
            dres = {"graphs_per_sec": round(dense_batch / dstep_s, 1),
                    "step_ms": round(dstep_s * 1e3, 3)}
            dres.update(_roofline(dstep, dstate, dbatch, dstep_s))
            # under the fused backend the CFConv filter network runs
            # inside the gather-multiply kernels (ops/scf_mp.py), which
            # hides its E*F^2 flops in a Pallas call that XLA's cost
            # model cannot see — take the useful-flops basis from the
            # composed-twin program (the scatter backend: identical
            # math/params) at TIGHT edge padding (real-edge work only).
            from hydragnn_tpu.ops.scf_mp import SCF_F_LIMIT

            dres["flops_method"] = "XLA cost model of the timed program"
            prior = os.environ.get("HYDRAGNN_AGGR_BACKEND")
            if prior == "fused" and hidden <= SCF_F_LIMIT:
                os.environ["HYDRAGNN_AGGR_BACKEND"] = "scatter"
                try:
                    key = (hidden, dense_batch)
                    if key not in twin_flops:
                        cstate, cbatch, cstep, _c, _s2, _h2 = _build(
                            hidden=hidden, dtype="bfloat16",
                            batch_size=dense_batch, tight_edges=True)
                        twin_flops[key] = _cost_flops(
                            cstep, cstate, cbatch)
                    fl = twin_flops[key]
                    dres["flops_per_step"] = round(fl)
                    dres["achieved_tflops"] = round(
                        fl / dstep_s / 1e12, 3)
                    dres["mfu_pct"] = round(
                        fl / dstep_s / _mxu_peak() * 100, 2)
                    dres["flops_method"] = (
                        "useful-flops basis from the composed-twin "
                        "program at TIGHT edge padding (real-edge "
                        "work only; the fused CFConv pipeline's "
                        "Pallas call is opaque to the XLA cost "
                        "model, and padding-edge flops are not "
                        "useful work)")
                    if not tight:
                        # r03/r04-comparable basis: loose twin
                        cstate2, cbatch2, cstep2, _c2, _s3, _h3 = \
                            _build(hidden=hidden, dtype="bfloat16",
                                   batch_size=dense_batch)
                        fl2 = _cost_flops(cstep2, cstate2, cbatch2)
                        dres["mfu_pct_loose_twin"] = round(
                            fl2 / dstep_s / _mxu_peak() * 100, 2)
                finally:
                    os.environ["HYDRAGNN_AGGR_BACKEND"] = prior
            name = (f"SchNet-h{hidden}-bf16-b{dense_batch}"
                    + ("-tight" if tight else ""))
            dense[name] = dres
            dense_c[f"h{hidden}" + ("t" if tight else "")] = {
                "gps": round(dres["graphs_per_sec"]),
                "mfu": dres["mfu_pct"]}
            print(f"bench: dense h{hidden} b{dense_batch}"
                  f"{' tight' if tight else ''} "
                  f"{dres['achieved_tflops']} TF ({dres['mfu_pct']}% "
                  f"MFU) {time.perf_counter() - t0:.1f}s",
                  file=sys.stderr)
            evidence["dense"] = dict(dense)
            compact["dense"] = dict(dense_c)
            compact["mfu_pct"] = max(
                v["mfu"] for v in dense_c.values())
            emit()
            _release_device()

    if want("sustained_default", _EST["sustained_default"]):
        # out-of-the-box run_training: NO env knobs; _auto_pipeline picks
        # scan/residency, val/test epochs run (round-4 default-path number)
        t0 = time.perf_counter()
        sd = _sustained(samples, heads, default_path=True)
        evidence["sustained_default"] = sd
        compact["sustained_gps"] = round(sd["graphs_per_sec"])
        print(f"bench: sustained_default {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
        emit()
        _release_device()

    if "archs" in phases:
        sweep = {}
        sweep_c = {}
        # From round 5 the sweep runs at TIGHT edge padding — the layout
        # the (now default-on) bucketed loader ships; the old worst-case
        # padding spent ~half of every edge-space stream on padding (the
        # loose-vs-tight bridge table lives in docs/PERF.md round 5,
        # measured from the full loose sweep of the same session).
        # ORDER: expensive uncacheable-compile rows (DimeNet) and the
        # VERDICT-gated rows come FIRST so a deadline squeeze skips the
        # cheap cache-hit tail, not the adjudicated numbers.
        # DimeNet-bf16: user-selectable mixed_precision run of the
        # slow-tail arch.  GAT-h128: the at-width zoo row (round-4
        # VERDICT item 8) — the fused GATv2 kernel's width win.
        # GAT-h256: hf=1536 — above one kernel call's FUSED_HF_LIMIT, so
        # this row measures the head-group TILED fused path that used to
        # silently fall back to the composed segment ops.
        from hydragnn_tpu.models.create import ALL_ARCHS
        from hydragnn_tpu.telemetry import pipeline as tele_pipeline

        order = ["DimeNet"]
        if dtype != "bfloat16":
            order.append("DimeNet-bf16")
        order += ["GAT", "GAT-h128", "GAT-h256"] + [
            a for a in ALL_ARCHS if a not in ("DimeNet", "GAT")]
        fallback_archs = []
        for arch in order:
            est = _arch_est(arch)
            if _deadline_remaining() < est:
                skipped.append(f"arch_{arch}")
                continue
            t0 = time.perf_counter()
            adtype = dtype
            hidden = 64
            tight = True
            arch_model = arch
            if arch.endswith("-bf16"):
                arch_model, adtype = arch[:-5], "bfloat16"
            elif arch.endswith("-h128"):
                arch_model, hidden = arch[:-5], 128
            elif arch.endswith("-h256"):
                arch_model, hidden = arch[:-5], 256
            disp0 = tele_pipeline.dispatch_snapshot()
            astate, abatch, astep, acfg, _s, _h = _build(
                model_type=arch_model, hidden=hidden, dtype=adtype,
                tight_edges=tight)
            astep_s, astate = _chip_loop(
                astate, abatch, astep, max(n_iters // 4, 2),
                max(n_repeats - 1, 1))
            backend = _dispatch_backend(
                disp0, tele_pipeline.dispatch_snapshot())
            sweep[arch] = {
                "graphs_per_sec": round(512 / astep_s, 1),
                "step_ms": round(astep_s * 1e3, 3),
                "aggr_backend": backend,
            }
            if not arch.endswith("-loose"):
                sweep_c[arch] = round(512 / astep_s)
            # the silent-fallback signal: the fused backend was
            # requested but this arch's traces took scatter paths
            if (os.environ.get("HYDRAGNN_AGGR_BACKEND") == "fused"
                    and backend != "fused"):
                fallback_archs.append(arch)
            print(f"bench: arch {arch} {512 / astep_s:,.0f} g/s "
                  f"aggr={backend} "
                  f"({time.perf_counter() - t0:.1f}s)", file=sys.stderr)
            _release_device()
            evidence["archs"] = dict(sweep)
            compact["archs"] = dict(sweep_c)
            # which archs ran on the fused aggregation path — the record
            # bench.py --dense / teleview --bench hold mainline archs to
            evidence["fused_archs"] = sorted(
                a for a, r in sweep.items()
                if r.get("aggr_backend") == "fused")
            compact["fused_archs"] = list(evidence["fused_archs"])
            if fallback_archs:
                evidence["aggr_fallback_archs"] = list(fallback_archs)
                compact["aggr_fallback"] = list(fallback_archs)
            emit()

    if want("sustained", _EST["sustained"]):
        t0 = time.perf_counter()
        evidence["sustained"] = _sustained(samples, heads)
        print(f"bench: sustained {time.perf_counter() - t0:.1f}s",
              file=sys.stderr)
        emit()
    # unconditional final emit: deadline-skipped phases must still be
    # visible in the LAST line even when no later phase emitted
    emit()


# ---------------------------------------------------------------------------
# --dense: acceptance bound over the dense ladder + per-arch sweep
# ---------------------------------------------------------------------------

# A mainline rung of the dense ladder below its MFU floor means the run
# was NOT compute-dense — it silently regressed to a stream/dispatch-
# bound program (ROADMAP item 2's gap).  Floors are PER RUNG, calibrated
# ~20-30% under the recorded v5e ladder (5.29 / 13.67 / 25.22-28.17%
# MFU for h256 / h512 / h1024): the bound catches falling OFF a fused
# path, not ordinary round-over-round noise, and the wider rungs no
# longer hide behind the blanket 5% the narrow rung needs.
DENSE_MFU_FLOORS = {
    "SchNet-h256": 5.0,
    "SchNet-h512": 10.0,
    "SchNet-h1024": 20.0,
}
# fallback floor for rungs with no per-arch entry (and the floor the
# h256 rung sits at — its recorded MFU is 5.29%)
DENSE_MFU_FLOOR = 5.0


def _rung_floor(name: str) -> float:
    """MFU floor for a dense-ladder rung: longest matching prefix in
    :data:`DENSE_MFU_FLOORS`, else the blanket :data:`DENSE_MFU_FLOOR`."""
    best, blen = DENSE_MFU_FLOOR, -1
    for prefix, floor in DENSE_MFU_FLOORS.items():
        if ((name == prefix or name.startswith(prefix + "-"))
                and len(prefix) > blen):
            best, blen = floor, len(prefix)
    return best


# archs whose interaction block has its own fused Pallas path at the
# sweep's mainline widths (SchNet CFConv pipeline, GATv2 attention,
# EGNN EGCL block, CGCNN gated-sum block — all specs of the
# ops/fused_block.py builder) — the set --dense holds to the
# fused-dispatch bound.  The other stacks ride the generic
# gather/scatter kernels and are covered by the MFU floor alone.
MAINLINE_FUSED_ARCHS = ("SchNet", "GAT", "EGNN", "CGCNN")


def dense_gate(evidence):
    """Pure acceptance bound over a bench evidence dict (the
    ``BENCH_evidence.json`` a bench run writes): every dense-ladder rung
    must clear its per-rung MFU floor (:data:`DENSE_MFU_FLOORS`, falling
    back to :data:`DENSE_MFU_FLOOR`), and every
    :data:`MAINLINE_FUSED_ARCHS` row of the per-arch sweep must report
    ``aggr_backend == "fused"`` — the trace-time dispatch tally
    (telemetry/pipeline.py), so an arch that silently fell back to the
    composed scatter ops FAILS instead of shipping a slow number.

    Returns ``(ok, failures, table)``; pure (no jax, no device) so the
    tier-1 suite can pin the verdict on synthetic evidence, and
    tools/teleview.py can render the same bound as WARNINGs."""
    failures = []
    table = []
    for name, row in sorted((evidence.get("dense") or {}).items()):
        if "error" in row:
            failures.append(f"dense rung {name}: {row['error']}")
            continue
        mfu = row.get("mfu_pct")
        floor = _rung_floor(name)
        table.append({"kind": "dense", "name": name, "mfu_pct": mfu,
                      "mfu_floor": floor,
                      "graphs_per_sec": row.get("graphs_per_sec")})
        if mfu is None:
            failures.append(
                f"dense rung {name}: no mfu_pct (roofline failed)")
        elif mfu < floor:
            failures.append(
                f"dense rung {name}: {mfu}% MFU < {floor}% "
                "floor — the run is not compute-dense")
    for arch, row in sorted((evidence.get("archs") or {}).items()):
        mainline = arch.split("-")[0] in MAINLINE_FUSED_ARCHS
        if "error" in row:
            if mainline:
                failures.append(f"arch {arch}: {row['error']}")
            continue
        backend = row.get("aggr_backend")
        table.append({"kind": "arch", "name": arch,
                      "graphs_per_sec": row.get("graphs_per_sec"),
                      "aggr_backend": backend})
        if mainline and backend != "fused":
            failures.append(
                f"arch {arch}: aggr_backend={backend} — silently fell "
                "off its fused path")
    if not table:
        failures.append("no dense/archs evidence (run bench's dense and "
                        "archs phases first)")
    return not failures, failures, table


def _retrace_dispatch(evidence) -> int:
    """Fill in the ``aggr_backend`` column for recorded arch rows that
    predate the trace-time dispatch tally.  Re-TRACES each such arch at
    the sweep's exact shapes (same ``_build``, abstract init only —
    nothing executes, so the recorded timing numbers are untouched)
    under the sweep's ``HYDRAGNN_AGGR_BACKEND=fused`` request, and
    records the backend the trace actually dispatched to.  Sound off-
    chip: the fused/scatter decision is made at trace time from static
    facts (width gates, sender_perm presence, env) — a CPU retrace
    reports the same branch the TPU sweep took."""
    from hydragnn_tpu.telemetry import pipeline as tele_pipeline

    archs = evidence.get("archs") or {}
    prior = os.environ.get("HYDRAGNN_AGGR_BACKEND")
    os.environ["HYDRAGNN_AGGR_BACKEND"] = "fused"
    changed = 0
    try:
        for arch, row in sorted(archs.items()):
            if "error" in row or row.get("aggr_backend") is not None:
                continue
            adtype, hidden, arch_model = "float32", 64, arch
            if arch.endswith("-bf16"):
                arch_model, adtype = arch[:-5], "bfloat16"
            elif arch.endswith("-h128"):
                arch_model, hidden = arch[:-5], 128
            elif arch.endswith("-h256"):
                arch_model, hidden = arch[:-5], 256
            before = tele_pipeline.dispatch_snapshot()
            try:
                _build(model_type=arch_model, hidden=hidden, dtype=adtype,
                       tight_edges=True, trace_only=True)
            except Exception as e:  # noqa: BLE001
                print(f"bench --dense: retrace {arch} failed: {e!r}",
                      file=sys.stderr)
                continue
            row["aggr_backend"] = _dispatch_backend(
                before, tele_pipeline.dispatch_snapshot())
            row["aggr_backend_method"] = (
                "trace-time dispatch tally, retraced without execution")
            changed += 1
            print(f"bench --dense: retrace {arch}: "
                  f"aggr={row['aggr_backend']}", file=sys.stderr)
    finally:
        if prior is None:
            os.environ.pop("HYDRAGNN_AGGR_BACKEND", None)
        else:
            os.environ["HYDRAGNN_AGGR_BACKEND"] = prior
    if changed:
        evidence["fused_archs"] = sorted(
            a for a, r in archs.items()
            if r.get("aggr_backend") == "fused")
    return changed


def _dense_main(argv) -> int:
    """``python bench.py --dense``: evaluate :func:`dense_gate` over the
    last bench run's evidence file, print the per-rung/per-arch table,
    and exit 1 on any violated bound (CI-pluggable acceptance check)."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --dense")
    ap.add_argument("--evidence", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_evidence.json"),
        help="evidence JSON from a prior bench run")
    ap.add_argument("--retrace-dispatch", action="store_true",
                    help="re-derive the aggr_backend column of recorded "
                         "arch rows by re-TRACING each arch's program "
                         "(no execution, no timing numbers touched) and "
                         "write it back — upgrades evidence recorded "
                         "before the dispatch tally existed")
    args = ap.parse_args(argv)

    if not os.path.exists(args.evidence):
        print(f"bench --dense: no evidence at {args.evidence} — run "
              "`python bench.py` (dense,archs phases) first",
              file=sys.stderr)
        return 2
    with open(args.evidence) as f:
        evidence = json.load(f)
    if args.retrace_dispatch:
        changed = _retrace_dispatch(evidence)
        if changed:
            with open(args.evidence, "w") as f:
                json.dump(evidence, f, indent=1)
            print(f"bench --dense: retraced dispatch for {changed} arch "
                  f"row(s), evidence updated", file=sys.stderr)
    ok, failures, table = dense_gate(evidence)
    fused_archs = sorted(
        row["name"] for row in table
        if row["kind"] == "arch" and row["aggr_backend"] == "fused")
    for row in table:
        if row["kind"] == "dense":
            print(f"bench --dense: rung {row['name']}: "
                  f"{row['mfu_pct']}% MFU (floor {row['mfu_floor']}%), "
                  f"{row['graphs_per_sec']} g/s", file=sys.stderr)
        else:
            print(f"bench --dense: arch {row['name']}: "
                  f"{row['graphs_per_sec']} g/s "
                  f"aggr={row['aggr_backend']}", file=sys.stderr)
    for fmsg in failures:
        print(f"bench --dense: FAIL {fmsg}", file=sys.stderr)
    print(json.dumps({
        "dense_gate": "PASS" if ok else "FAIL",
        "mfu_floor": DENSE_MFU_FLOOR,
        "mfu_floors": DENSE_MFU_FLOORS,
        "mainline_fused_archs": list(MAINLINE_FUSED_ARCHS),
        "fused_archs": fused_archs,
        "failures": failures,
    }, separators=(",", ":")))
    return 0 if ok else 1


# ---------------------------------------------------------------------------
# --zero: ZeRO sharded-training ladder (bytes per device + throughput)
# ---------------------------------------------------------------------------


def _zero_main(argv) -> int:
    """``python bench.py --zero``: measure per-device resident param /
    optimizer-state bytes and step throughput for the dense h256/h512/h1024
    ladder under replicated DP vs ZeRO-1 vs ZeRO-2 on the current mesh
    (docs/SCALING.md §4).  Bytes rows are exact (analytic from the placed
    shardings, cross-checked against the MEASURED per-device shard bytes);
    throughput rows are best-effort on CPU (the MEMORY ratio, not CPU
    walltime, is the deliverable off-TPU).  Writes BENCH_zero.json and
    prints one compact JSON line."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --zero")
    ap.add_argument("--hidden", default="256,512,1024",
                    help="comma ladder of hidden widths")
    ap.add_argument("--batch", type=int, default=8,
                    help="graphs per DEVICE per step")
    ap.add_argument("--steps", type=int, default=3,
                    help="timed steps per mode (0 = bytes only)")
    ap.add_argument("--max-timed-hidden", type=int, default=None,
                    help="skip throughput timing above this width "
                         "(default: 512 on CPU, unlimited on TPU)")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_zero.json"))
    args = ap.parse_args(argv)

    # the ladder needs a multi-device mesh to shard across — force a
    # virtual 8-device host mesh unless the env already decided
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

    import jax
    import numpy as np

    from hydragnn_tpu.parallel.mesh import (
        make_dp_train_step,
        make_mesh,
        replicate_state,
        stack_batches,
    )
    from hydragnn_tpu.parallel.zero import (
        measured_device_bytes,
        sharding_report,
        zero_shard_state,
    )

    devs = jax.devices()
    on_tpu = devs[0].platform == "tpu"
    n_dev = len(devs)
    max_timed = args.max_timed_hidden or (10**9 if on_tpu else 512)
    mesh = make_mesh()
    dtype = "bfloat16" if on_tpu else "float32"
    print(f"bench --zero: platform={devs[0].platform} devices={n_dev} "
          f"dtype={dtype}", file=sys.stderr)

    rows = {}
    compact_rows = {}
    for hidden in [int(h) for h in args.hidden.split(",") if h.strip()]:
        state, batch, _step, cfg, _s, _h = _build(
            hidden=hidden, dtype=dtype, batch_size=args.batch,
            tight_edges=True)
        from hydragnn_tpu.models.create import create_model
        from hydragnn_tpu.train.optimizer import select_optimizer

        model = create_model(cfg)
        opt_spec = select_optimizer(BENCH_OPTIMIZER)
        # host copies: each mode re-places them, and the per-rung
        # _release_device (which deletes EVERY live device array) must not
        # invalidate the state the next rung's modes start from
        state = jax.device_get(state)
        stacked = jax.device_get(stack_batches([batch] * n_dev))
        row = {}
        prev_params = None
        for mode, stage in (("replicated", 0), ("zero1", 1), ("zero2", 2)):
            if stage == 0:
                st = replicate_state(state, mesh)
                zs = None
            else:
                st, zs = zero_shard_state(state, mesh, stage=stage)
            rep = sharding_report(st, zs)
            dev0 = mesh.devices.flat[0]
            rep["param_bytes_per_device_measured"] = measured_device_bytes(
                st.params, dev0)
            rep["opt_bytes_per_device_measured"] = measured_device_bytes(
                st.opt_state, dev0)
            mrow = {k: rep[k] for k in (
                "param_bytes_per_device", "opt_bytes_per_device",
                "param_bytes_replicated", "opt_bytes_replicated",
                "param_bytes_per_device_measured",
                "opt_bytes_per_device_measured",
                "padded_waste_bytes_per_device")}
            mrow["resident_bytes_per_device"] = (
                rep["param_bytes_per_device"] + rep["opt_bytes_per_device"])
            if args.steps > 0 and hidden <= max_timed:
                dp_step = make_dp_train_step(
                    model, cfg, opt_spec, mesh, zero_specs=zs)
                t0 = time.perf_counter()
                st, m = dp_step(st, stacked)
                _sync(m["loss"])
                mrow["compile_plus_first_step_s"] = round(
                    time.perf_counter() - t0, 3)
                # the parity evidence: the FIRST step from identical state
                # is bit-comparable across modes; later free-running steps
                # accumulate cross-program fusion jitter
                mrow["loss_first_step"] = float(m["loss"])
                t0 = time.perf_counter()
                for _ in range(args.steps):
                    st, m = dp_step(st, stacked)
                _sync(m["loss"])
                dt = (time.perf_counter() - t0) / args.steps
                mrow["step_ms"] = round(dt * 1e3, 2)
                mrow["graphs_per_sec"] = round(args.batch * n_dev / dt, 1)
                # parity anchor: every mode's params after the same K steps
                if stage > 0:
                    from hydragnn_tpu.parallel.zero import consolidate_state

                    st = consolidate_state(st, zs, mesh)
                leaves = [np.asarray(x) for x in
                          jax.tree_util.tree_leaves(jax.device_get(st.params))]
                if prev_params is not None:
                    mrow["params_match_replicated"] = bool(all(
                        np.allclose(a, b, rtol=1e-4, atol=1e-6)
                        for a, b in zip(prev_params, leaves)))
                else:
                    prev_params = leaves
            row[mode] = mrow
            print(f"bench --zero: h{hidden} {mode}: "
                  f"opt {mrow['opt_bytes_per_device']/1e6:.2f} MB/dev "
                  f"(repl {mrow['opt_bytes_replicated']/1e6:.2f}), "
                  f"params {mrow['param_bytes_per_device']/1e6:.2f} MB/dev"
                  + (f", {mrow.get('graphs_per_sec', 0)} g/s"
                     if "graphs_per_sec" in mrow else ""), file=sys.stderr)
        _release_device()  # rung boundary: all live device arrays dropped
        rows[f"h{hidden}"] = row
        o_r = row["replicated"]["opt_bytes_per_device"]
        o_z = row["zero1"]["opt_bytes_per_device"]
        compact_rows[f"h{hidden}"] = {
            "opt_mb_repl": round(o_r / 1e6, 2),
            "opt_mb_z1": round(o_z / 1e6, 2),
            "ratio": round(o_z / max(o_r, 1), 4),
        }
    result = {
        "metric": "zero_sharding_bytes",
        "unit": "bytes/device",
        "platform": devs[0].platform,
        "devices": n_dev,
        "zero_axis_size": n_dev,
        "batch_per_device": args.batch,
        "dtype": dtype,
        "ladder": rows,
    }
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=1)
    os.replace(tmp, args.out)
    print(json.dumps({"metric": "zero_sharding_bytes", "devices": n_dev,
                      "ladder": compact_rows,
                      "evidence": os.path.basename(args.out)}))
    return 0


# ---------------------------------------------------------------------------
# --comms: comm-vs-compute split of the sharded train steps
# ---------------------------------------------------------------------------


def _comms_main(argv) -> int:
    """``python bench.py --comms``: per-step comm-vs-compute attribution
    for the mesh DP / ZeRO-1 / ZeRO-2 train steps on the current mesh
    (forced 8-device host mesh off-TPU), via the telemetry A/B probe
    (hydragnn_tpu/telemetry/comms.py): the annotated full step is timed
    against a collective-only shard_map replay of its pmean/all_gather
    volume.  comm_pct rows are an upper bound on the collective's
    critical-path share (overlap is not subtracted); on CPU the absolute
    times are best-effort — the DELIVERABLE off-TPU is that the split is
    measured and lands in the manifest/bench evidence at all.  Writes
    BENCH_comms.json and prints one compact JSON line."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --comms")
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8,
                    help="graphs per DEVICE per step")
    ap.add_argument("--iters", type=int, default=3,
                    help="timed iterations per program")
    ap.add_argument("--modes", default="dp,zero1,zero2",
                    help="comma subset of dp,zero1,zero2")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_comms.json"))
    args = ap.parse_args(argv)

    # the probe needs collectives to exist: force a virtual 8-device host
    # mesh unless the env already decided
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

    import jax

    from hydragnn_tpu.parallel.mesh import (
        make_mesh,
        replicate_state,
        stack_batches,
    )
    from hydragnn_tpu.parallel.zero import zero_shard_state
    from hydragnn_tpu.telemetry.comms import dp_comms_probe

    devs = jax.devices()
    n_dev = len(devs)
    mesh = make_mesh()
    dtype = "bfloat16" if devs[0].platform == "tpu" else "float32"
    print(f"bench --comms: platform={devs[0].platform} devices={n_dev} "
          f"dtype={dtype}", file=sys.stderr)

    state, batch, _step, cfg, _s, _h = _build(
        hidden=args.hidden, dtype=dtype, batch_size=args.batch,
        tight_edges=True)
    from hydragnn_tpu.models.create import create_model
    from hydragnn_tpu.train.optimizer import select_optimizer

    model = create_model(cfg)
    opt_spec = select_optimizer(BENCH_OPTIMIZER)
    state = jax.device_get(state)  # host copy: each mode re-places it
    stacked = jax.device_get(stack_batches([batch] * n_dev))

    rows = {}
    compact = {}
    for mode in [m.strip() for m in args.modes.split(",") if m.strip()]:
        if mode == "dp":
            st, zs = replicate_state(state, mesh), None
        elif mode in ("zero1", "zero2"):
            st, zs = zero_shard_state(state, mesh,
                                      stage=1 if mode == "zero1" else 2)
        else:
            print(f"bench --comms: unknown mode {mode!r} skipped",
                  file=sys.stderr)
            continue
        split = dp_comms_probe(model, cfg, opt_spec, mesh, st, stacked,
                               zero_specs=zs, iters=args.iters)
        rows[mode] = split
        compact[mode] = {"step_ms": split["step_ms"],
                         "comm_ms": split["comm_ms"],
                         "comm_pct": split["comm_pct"]}
        print(f"bench --comms: {mode}: step {split['step_ms']:.2f} ms, "
              f"comm {split['comm_ms']:.2f} ms ({split['comm_pct']}%)",
              file=sys.stderr)
        _release_device()  # mode boundary: drop all live device arrays

    result = {
        "metric": "comm_vs_compute_split",
        "unit": "ms/step",
        "platform": devs[0].platform,
        "devices": n_dev,
        "hidden": args.hidden,
        "batch_per_device": args.batch,
        "dtype": dtype,
        "modes": rows,
    }
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=1)
    os.replace(tmp, args.out)
    print(json.dumps({"metric": "comm_vs_compute_split", "devices": n_dev,
                      "modes": compact,
                      "evidence": os.path.basename(args.out)}))
    return 0


# ---------------------------------------------------------------------------
# --giant: halo graph-sharding ladder (one giant graph across the mesh)
# ---------------------------------------------------------------------------


def _giant_main(argv) -> int:
    """``python bench.py --giant``: train ONE synthetic giant graph (3D
    lattice, 6-neighbor edges — the mesh-scale / charge-density input
    class) across the device mesh at 4-32x a nominal per-device node
    budget, and measure the halo backend's memory curve against the
    analytic ``N/D + halo`` model AND the gspmd fallback's full-[N, F]
    replication (docs/SCALING.md §6).  Bytes rows are exact (measured
    per-device shard bytes + compiled-HLO buffer dims); step times are
    best-effort on CPU.  Writes BENCH_graph_shard.json."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py --giant")
    ap.add_argument("--grid", default="16,20,26,32",
                    help="comma ladder of lattice sides k (N = k^3)")
    ap.add_argument("--budget-nodes", type=int, default=1024,
                    help="nominal per-device node budget the ladder is "
                         "expressed against")
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument("--features", type=int, default=16)
    ap.add_argument("--steps", type=int, default=3,
                    help="timed steps per backend (0 = bytes only)")
    ap.add_argument("--gspmd-max-nodes", type=int, default=10000,
                    help="skip the gspmd baseline above this N (its CPU "
                         "compile of the full graph is the slow part)")
    ap.add_argument("--method", default="sfc")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        "BENCH_graph_shard.json"))
    args = ap.parse_args(argv)

    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

    import re

    import jax
    import numpy as np

    from hydragnn_tpu.graph.partition import (
        shard_batch_halo,
        synthetic_lattice_batch,
    )
    from hydragnn_tpu.models.base import ModelConfig, NodeHeadCfg
    from hydragnn_tpu.models.create import create_model
    from hydragnn_tpu.parallel.graph_shard import (
        make_gspmd_train_step,
        shard_batch,
    )
    from hydragnn_tpu.parallel.mesh import (
        make_halo_train_step,
        make_mesh,
        replicate_state,
    )
    from hydragnn_tpu.parallel.zero import measured_device_bytes
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.trainer import create_train_state
    from jax.sharding import NamedSharding, PartitionSpec as P

    devs = jax.devices()
    n_dev = len(devs)
    mesh = make_mesh()
    F = args.features

    cfg = ModelConfig(
        model_type="SAGE", input_dim=F, hidden_dim=args.hidden,
        output_dim=(1,), output_type=("node",), graph_head=None,
        node_head=NodeHeadCfg(1, (args.hidden,), "mlp"),
        task_weights=(1.0,), num_conv_layers=2)
    model = create_model(cfg)
    opt = select_optimizer(BENCH_OPTIMIZER)

    def node_dims(text):
        return {int(m.group(1))
                for m in re.finditer(r"f32\[(\d+),(\d+)\]", text)}

    rows = {}
    compact = {}
    for k in [int(v) for v in args.grid.split(",") if v.strip()]:
        batch = synthetic_lattice_batch(k, features=F)
        n_real = k ** 3
        n_full = batch.x.shape[0]
        hb, plan = shard_batch_halo(batch, n_dev, method=args.method,
                                    hops=cfg.num_conv_layers,
                                    head_types=["node"])
        state = create_train_state(model, batch, opt, seed=0)

        sharded_x = jax.device_put(
            np.asarray(hb.x), NamedSharding(mesh, P(mesh.axis_names[0])))
        halo_node_bytes = measured_device_bytes(
            sharded_x, mesh.devices.flat[0])
        repl_node_bytes = n_full * F * 4
        analytic_rows = plan.n_local + n_dev * plan.halo_pair
        row = {
            "n_nodes": n_real,
            "n_edges": int(plan.stats["n_edges_real"]),
            "budget_multiple": round(n_real / (args.budget_nodes * 1.0), 1),
            "partition": plan.stats,
            "node_feature_bytes_per_device_halo": int(halo_node_bytes),
            "node_feature_bytes_replicated": int(repl_node_bytes),
            "residency_rows_local": int(plan.n_local),
            "residency_rows_with_halo": int(analytic_rows),
            "residency_model_rows": int(-(-n_real // n_dev)
                                        + plan.stats["halo_rows_max"]),
        }

        steph = make_halo_train_step(model, cfg, opt, mesh)
        s_h = replicate_state(state, mesh)
        t0 = time.perf_counter()
        lowered = steph.lower(s_h, hb).compile()
        hlo_halo = lowered.as_text()
        # the no-full-buffer claim: the compiled halo step must contain NO
        # tensor with the full padded node count as a dimension (the same
        # assertion tests/test_graph_shard.py pins); node-array residency
        # in its HLO is ext_n rows
        row["halo_full_array_buffers"] = sorted(
            d for d in node_dims(hlo_halo) if d == n_full)
        row["halo_hlo_node_rows"] = int(plan.ext_n)
        # node-row headroom: full-[N, F] replication (what gspmd
        # materializes per device) over the halo step's extended rows
        row["memory_headroom_node_rows"] = round(
            n_full / plan.ext_n, 2)
        if args.steps > 0:
            s_h, m = lowered(s_h, hb)
            _sync(m["loss"])
            row["halo_compile_plus_first_step_s"] = round(
                time.perf_counter() - t0, 3)
            row["halo_loss_first_step"] = float(m["loss"])
            t0 = time.perf_counter()
            for _ in range(args.steps):
                s_h, m = lowered(s_h, hb)
            _sync(m["loss"])
            row["halo_step_ms"] = round(
                (time.perf_counter() - t0) / args.steps * 1e3, 2)

        if n_real <= args.gspmd_max_nodes:
            stepg = make_gspmd_train_step(model, cfg, opt, mesh)
            sb = shard_batch(batch, mesh)
            s_g = replicate_state(state, mesh)
            t0 = time.perf_counter()
            lg = stepg.lower(s_g, sb).compile()
            hlo_g = lg.as_text()
            # the baseline's failure mode, as compiled evidence: the full
            # [N, F] node buffer IS materialized (the GSPMD all-gather)
            row["gspmd_has_full_array"] = bool(
                n_full in node_dims(hlo_g))
            row["memory_headroom_vs_gspmd"] = round(
                n_full / plan.ext_n, 2) if row["gspmd_has_full_array"] \
                else None
            if args.steps > 0:
                s_g, mg = lg(s_g, sb)
                _sync(mg["loss"])
                row["gspmd_compile_plus_first_step_s"] = round(
                    time.perf_counter() - t0, 3)
                row["gspmd_loss_first_step"] = float(mg["loss"])
                row["loss_match"] = bool(np.isclose(
                    row.get("halo_loss_first_step", np.nan),
                    float(mg["loss"]), rtol=1e-5))
                t0 = time.perf_counter()
                for _ in range(args.steps):
                    s_g, mg = lg(s_g, sb)
                _sync(mg["loss"])
                row["gspmd_step_ms"] = round(
                    (time.perf_counter() - t0) / args.steps * 1e3, 2)
        _release_device()
        rows[f"n{n_real}"] = row
        compact[f"n{n_real}"] = {
            "rows_dev": int(analytic_rows),
            "rows_repl": n_full,
            "ratio": round(analytic_rows / n_full, 4),
            **({"headroom": row["memory_headroom_vs_gspmd"]}
               if "memory_headroom_vs_gspmd" in row else {}),
        }
        print(f"bench --giant: N={n_real} ({row['budget_multiple']}x "
              f"budget): {analytic_rows} rows/dev vs {n_full} replicated "
              f"({analytic_rows / n_full:.3f}x), cut "
              f"{plan.stats['cut_edge_pct']}%, halo max "
              f"{plan.stats['halo_rows_max']}"
              + (f", headroom {row['memory_headroom_vs_gspmd']}x vs gspmd"
                 if "memory_headroom_vs_gspmd" in row else "")
              + (f", loss match {row.get('loss_match')}"
                 if "loss_match" in row else ""), file=sys.stderr)

    result = {
        "metric": "graph_shard_residency",
        "unit": "node rows/device",
        "platform": devs[0].platform,
        "devices": n_dev,
        "method": args.method,
        "hops": cfg.num_conv_layers,
        "hidden": args.hidden,
        "features": F,
        "budget_nodes_per_device": args.budget_nodes,
        "ladder": rows,
    }
    tmp = args.out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=1)
    os.replace(tmp, args.out)
    print(json.dumps({"metric": "graph_shard_residency",
                      "devices": n_dev, "ladder": compact,
                      "evidence": os.path.basename(args.out)}))
    return 0


# ---------------------------------------------------------------------------
# parent
# ---------------------------------------------------------------------------


def _try_child(platform: str, timeout: float):
    """Run the ONE measurement child, TEEING its stdout through live (a
    buffered parent loses every finished phase when an outer kill takes
    the parent — teed lines are already on the captured stdout the moment
    the child emits them).  Returns (last parsed headline line or None,
    child return code)."""
    import threading

    env = dict(os.environ)
    if platform == "cpu":
        env["JAX_PLATFORMS"] = "cpu"
    # absolute deadline for the child's phase guard, with teardown margin
    env["HYDRAGNN_BENCH_DEADLINE"] = repr(
        time.time() + max(timeout - 30.0, 60.0))

    p = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--child", platform],
        env=env, stdout=subprocess.PIPE, text=True, bufsize=1)
    holder = {}

    def pump():
        for line in p.stdout:
            line = line.rstrip("\n")
            if not line:
                continue
            print(line, flush=True)  # tee: survives an outer parent-kill
            try:
                d = json.loads(line)
                if d.get("metric") == METRIC:
                    holder["last"] = d
            except json.JSONDecodeError:
                pass

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    try:
        p.wait(timeout=timeout)
    except subprocess.TimeoutExpired:
        print(f"bench: {platform} child timed out after {timeout:.0f}s",
              file=sys.stderr)
        p.kill()
        p.wait()
    t.join(timeout=10)
    return holder.get("last"), p.returncode


def main() -> int:
    platform = os.getenv("HYDRAGNN_BENCH_PLATFORM", "tpu").lower()
    if platform not in ("tpu", "cpu"):
        print(f"bench: HYDRAGNN_BENCH_PLATFORM must be tpu or cpu, got "
              f"{platform!r}", file=sys.stderr)
        return 2
    timeout = float(os.getenv("HYDRAGNN_BENCH_TIMEOUT", "1380"))
    result, rc = _try_child(platform, timeout)
    if rc != 0 or result is None or not result.get("value"):
        # no fallback, no placeholder value: whatever finished phases the
        # child teed are on stdout, and the exit code says the run failed
        print(f"bench: {platform} child rc={rc}, headline value "
              f"{(result or {}).get('value')!r} — FAILED", file=sys.stderr)
        return rc if rc not in (0, None) else 1
    # re-print so the LAST stdout line is the complete cumulative record
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        _child(sys.argv[2] if len(sys.argv) > 2 else "tpu")
    elif len(sys.argv) > 1 and sys.argv[1] == "--zero":
        sys.exit(_zero_main(sys.argv[2:]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--comms":
        sys.exit(_comms_main(sys.argv[2:]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--giant":
        sys.exit(_giant_main(sys.argv[2:]))
    elif len(sys.argv) > 1 and sys.argv[1] == "--dense":
        sys.exit(_dense_main(sys.argv[2:]))
    else:
        sys.exit(main())
