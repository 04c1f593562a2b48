"""MetricsLogger: the host-side spine of the telemetry subsystem.

Design constraints (why this is not a naive per-step print):

- ZERO added device->host syncs on the hot path.  The trainer's epoch loop
  dispatches steps back-to-back and fetches ONE accumulator per epoch (a
  sync drains the dispatch queue — see train/trainer.py).  ``on_step``
  therefore only appends the step's DEVICE
  scalars + a host timestamp to a pending list; ``flush_steps`` fetches them
  all in one ``jax.device_get`` at epoch end and emits the JSONL records
  then.  Consequence: per-step ``step_time_s`` is dispatch-to-dispatch host
  wall time (under async dispatch that is queue-feed time, not device
  execution time; the epoch record's ``epoch_time_s`` is the authoritative
  wall clock).  What the device spends inside a step is a profiler
  trace's to say (docs/TELEMETRY.md "Tracing").

- Rank-0-gated sinks, all-rank collectives.  Every rank runs the logger
  (cross-rank reductions via ``parallel/comm.py`` host collectives must be
  entered by all processes or they deadlock); only rank 0 holds sinks.

- Derived perf accounting is computed from STATIC batch metadata (leaf
  shapes = the PadSpec bucket actually used) plus the in-jit real-count
  metrics, so padding-waste % is exact and free.  The in-run MFU estimate
  uses the SAME flops-basis helper as bench.py (telemetry/flops.py).
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from hydragnn_tpu.telemetry import counters, pipeline, programs
from hydragnn_tpu.telemetry.flops import (
    mfu_pct,
    peak_flops,
    shape_struct_tree,
    step_cost_flops,
)
from hydragnn_tpu.telemetry.sinks import Sink, TensorBoardSink, build_sinks
from hydragnn_tpu.utils import tracer
from hydragnn_tpu.utils.env import env_flag, env_int, env_str


@dataclasses.dataclass
class TelemetryConfig:
    """Parsed ``Telemetry`` config section + env knobs (env wins).

    Knobs: HYDRAGNN_TELEMETRY (enable), HYDRAGNN_TELEMETRY_SINKS
    (comma list: jsonl,csv,stdout), HYDRAGNN_TELEMETRY_DIR,
    HYDRAGNN_TELEMETRY_HEARTBEAT (stdout cadence, steps),
    HYDRAGNN_TRACE (span flight recorder, docs/TELEMETRY.md "Tracing"),
    HYDRAGNN_TRACE_RING (span ring/reservoir capacity).
    """

    enable: bool = False
    sinks: Tuple[str, ...] = ("jsonl", "stdout")
    dir: Optional[str] = None
    heartbeat: int = 50
    ring: int = 256
    mfu: bool = True
    trace: bool = False
    trace_ring: int = 512

    @staticmethod
    def from_section(section: Optional[Dict[str, Any]]) -> "TelemetryConfig":
        s = dict(section or {})
        d = TelemetryConfig()  # the dataclass IS the single default source
        sinks = s.get("sinks", ",".join(d.sinks))
        if isinstance(sinks, str):
            sinks = tuple(x.strip() for x in sinks.split(",") if x.strip())
        cfg = TelemetryConfig(
            enable=bool(int(s.get("enable", d.enable))),
            sinks=tuple(sinks),
            dir=s.get("dir"),
            heartbeat=int(s.get("heartbeat", d.heartbeat)),
            ring=int(s.get("ring", d.ring)),
            mfu=bool(int(s.get("mfu", d.mfu))),
            trace=bool(int(s.get("trace", d.trace))),
            trace_ring=int(s.get("trace_ring", d.trace_ring)),
        )
        # env overrides (the smoke-run contract: HYDRAGNN_TELEMETRY=1 turns
        # the subsystem on with no config edit)
        if "HYDRAGNN_TELEMETRY" in os.environ:
            cfg.enable = env_flag("HYDRAGNN_TELEMETRY")
        env_sinks = env_str("HYDRAGNN_TELEMETRY_SINKS", "")
        if env_sinks:
            cfg.sinks = tuple(
                x.strip() for x in env_sinks.split(",") if x.strip())
        cfg.dir = env_str("HYDRAGNN_TELEMETRY_DIR", cfg.dir or "") or cfg.dir
        if "HYDRAGNN_TELEMETRY_HEARTBEAT" in os.environ:
            cfg.heartbeat = env_int("HYDRAGNN_TELEMETRY_HEARTBEAT", 50)
        if "HYDRAGNN_TRACE" in os.environ:
            cfg.trace = env_flag("HYDRAGNN_TRACE")
        if "HYDRAGNN_TRACE_RING" in os.environ:
            cfg.trace_ring = env_int("HYDRAGNN_TRACE_RING", 512)
        return cfg


class RingBuffer:
    """Fixed-capacity window of recent step records with min/max/avg/last
    aggregation — the heartbeat's and manifest's rolling summary."""

    def __init__(self, capacity: int = 256):
        self._buf: collections.deque = collections.deque(
            maxlen=max(1, int(capacity)))

    def push(self, record: Dict[str, Any]) -> None:
        self._buf.append(record)

    def __len__(self) -> int:
        return len(self._buf)

    def aggregate(self) -> Dict[str, Dict[str, float]]:
        out: Dict[str, Dict[str, float]] = {}
        cols: Dict[str, List[float]] = {}
        for rec in self._buf:
            for k, v in rec.items():
                if isinstance(v, (int, float)) and not isinstance(v, bool):
                    cols.setdefault(k, []).append(float(v))
        for k, vals in cols.items():
            out[k] = {
                "min": min(vals),
                "max": max(vals),
                "avg": sum(vals) / len(vals),
                "last": vals[-1],
                "count": len(vals),
            }
        return out


def batch_pad_meta(batch) -> Dict[str, int]:
    """Padded slot counts of one dispatch unit, from STATIC leaf shapes.

    Works for plain batches ([N]-space leaves), device-stacked ([D, N]) and
    scan-chunked ([K, D, N]) superbatches: every leading axis multiplies the
    slot count, matching the in-jit real-count metrics which sum (and psum)
    over the same axes.
    """
    x = batch.x.shape            # (..., N, F)
    e = batch.senders.shape      # (..., E)
    g = batch.graph_mask.shape   # (..., G)
    lead = int(np.prod(x[:-2], dtype=np.int64)) if len(x) > 2 else 1
    return {
        "padded_nodes": lead * int(x[-2]),
        "padded_edges": int(np.prod(e, dtype=np.int64)),
        "padded_graphs": int(np.prod(g, dtype=np.int64)),
    }


def waste_pct(real: float, padded: float) -> float:
    """Fraction of padded slots that carried no real work, in percent."""
    if padded <= 0:
        return 0.0
    return max(0.0, (1.0 - float(real) / float(padded))) * 100.0


def _loader_padding_efficiency(loader) -> Optional[float]:
    """Walk a loader wrapper chain for the innermost
    ``padding_efficiency()`` (GraphDataLoader keeps real/padded node-slot
    counters per epoch)."""
    obj = loader
    while obj is not None:
        fn = getattr(obj, "padding_efficiency", None)
        if callable(fn):
            try:
                return float(fn())
            except Exception:  # graftlint: disable=ROB001 (duck-typed loader probe; absent metric reports None)
                return None
        obj = getattr(obj, "loader", None)
    return None


class MetricsLogger:
    """Unified per-step/per-epoch telemetry with pluggable sinks."""

    def __init__(self, cfg: Optional[TelemetryConfig] = None,
                 run_name: str = "run", out_dir: Optional[str] = None,
                 rank: int = 0, world_size: int = 1,
                 cross_rank: Optional[bool] = None,
                 names_device: bool = True):
        self.cfg = cfg or TelemetryConfig()
        self.run_name = run_name
        self.rank = int(rank)
        self.world_size = int(world_size)
        # cross-rank host collectives must be entered by EVERY process of
        # the global runtime; an ensemble branch (explicit sub-mesh) must
        # not attempt them — the other branch won't match the call.
        self.cross_rank = (self.world_size > 1 if cross_rank is None
                           else bool(cross_rank))
        self.run_id = f"{run_name}-{uuid.uuid4().hex[:8]}"
        # explicit config/env dir wins over the caller's default location
        self.out_dir = self.cfg.dir or out_dir or os.path.join(
            "./logs", run_name, "telemetry")
        self.ring = RingBuffer(self.cfg.ring)
        self.sinks: List[Sink] = []
        self._pending: List[Tuple[Any, Dict[str, int], float, tuple]] = []
        self._pending_avals: Dict[tuple, Any] = {}
        self._epoch = 0
        self._in_epochs = False     # the trainer has begun an epoch
        self._epoch_t0 = time.perf_counter()
        self._global_step = 0
        self._steps_dispatched = 0  # on_step's count: ahead of the flush
        self._dispatch = 0
        self._steps_per_item = 1
        self._step_fn = None
        self._state_avals = None
        self._flops_cache: Dict[tuple, Optional[float]] = {}
        self._mfu_broken = False
        self._dispatch_base: Dict[str, int] = {}
        # the device this process got and its published MFU peak (None for
        # a device outside telemetry/flops.py:DEVICE_PEAKS — then no
        # mfu_est_pct is emitted); resolved only when the subsystem is on.
        # ``names_device=False`` is for a process that must stay off JAX
        # (the subprocess fleet's router parent: a parent that has touched
        # JAX holds the chip its children need) — its records name none.
        self._device: Dict[str, Any] = {}
        self._peak: Optional[float] = None
        # resilience/serving health-event tally (step_skipped,
        # preempt_save, request_enqueued, ...) — folded into the manifest.
        # Lock-guarded: the trainer is single-threaded, but the serving
        # HTTP layer calls health() from per-connection handler threads
        # (an unlocked read-modify-write would drop counts under load)
        self._health_counts: Dict[str, int] = {}
        self._health_lock = threading.Lock()
        # per-flush serving step records (serve_step) get their own
        # monotonic counter — they interleave with training steps in
        # shared logs and must not perturb the trainer's step axis
        self._serve_steps = 0
        # parameter/opt-state sharding layout (log_sharding) — folded into
        # the end-of-run manifest
        self._sharding: Optional[Dict[str, Any]] = None
        # comm-vs-compute split (log_comms, the A/B probe verdict) —
        # folded into the manifest's ``comms`` block
        self._comms: Optional[Dict[str, Any]] = None
        # span flight recorder (telemetry/trace.py) — None when tracing is
        # off, so every call site's default-off path is a plain None check
        # (no recorder object, no span allocation: hot-path purity)
        self.spans = None
        if self.enabled and self.cfg.trace:
            from hydragnn_tpu.telemetry.trace import SpanRecorder

            self.spans = SpanRecorder(ring=self.cfg.trace_ring,
                                      emit=self.emit_threadsafe)
        if self.enabled and self.rank == 0:
            self.sinks = build_sinks(
                self.cfg.sinks, self.out_dir, self.run_id,
                heartbeat=self.cfg.heartbeat)
        if self.enabled:
            pipeline.set_enabled(True)
            # dispatch counts are cumulative for the process (trace-time
            # tally) — remember the baseline so the manifest reports THIS
            # run's fused/fallback decisions, not a prior HPO trial's
            self._dispatch_base = pipeline.dispatch_snapshot()
            from hydragnn_tpu.ops.aggregate import aggr_backend
            from hydragnn_tpu.utils.runtime import device_info

            if names_device:
                self._device = device_info()
                self._peak = peak_flops(self._device["device_kind"])
            self._emit({
                "event": "run_start",
                "run_id": self.run_id,
                "run_name": run_name,
                "rank": self.rank,
                "world_size": self.world_size,
                "t": time.time(),
                **self._device,
                "peak_flops_basis": self._peak,
                "sinks": list(self.cfg.sinks),
                "aggr_backend": aggr_backend(),
            })
        if self.enabled and self.sinks:
            # the programs built so far (create_train_state, resident
            # staging: before this logger) and every later one
            programs.RECORDER.attach(self)

    # -- construction helpers ------------------------------------------------

    @classmethod
    def disabled(cls) -> "MetricsLogger":
        return cls(TelemetryConfig(enable=False))

    @classmethod
    def from_env(cls, run_name: str = "run",
                 out_dir: Optional[str] = None, rank: int = 0,
                 world_size: int = 1,
                 cross_rank: Optional[bool] = None,
                 names_device: bool = True) -> "MetricsLogger":
        return cls(TelemetryConfig.from_section(None), run_name=run_name,
                   out_dir=out_dir, rank=rank, world_size=world_size,
                   cross_rank=cross_rank, names_device=names_device)

    @property
    def enabled(self) -> bool:
        return bool(self.cfg.enable)

    def attach_tensorboard(self, writer) -> None:
        """Route epoch/step scalars to an existing SummaryWriter (the
        trainer's pre-telemetry inline ``add_scalar`` calls, refactored into
        a sink).  Works even when step telemetry is disabled — TensorBoard
        epoch scalars are a base capability, not an opt-in."""
        if writer is not None and self.rank == 0:
            self.sinks.append(TensorBoardSink(writer))

    def bind_step(self, step_fn, state, steps_per_item: int = 1,
                  cost_model: bool = True) -> None:
        """Remember the jitted step and the train state's avals (captured
        BEFORE the first donated call, while buffers are alive) for the
        in-run MFU flops basis.  ``cost_model=False`` leaves the estimate
        out: a stack whose products run inside Pallas kernels that XLA's
        cost model cannot see (models/laguna.py) would pay a second
        compile of a large program for a number that is wrong."""
        self._steps_per_item = max(1, int(steps_per_item))
        # the flops basis costs a second XLA compile of the step (per
        # PadSpec bucket) — only the rank that actually writes records
        # (sinks exist) should pay it
        if not (self.enabled and self.cfg.mfu and self.sinks and cost_model):
            return
        self._step_fn = step_fn
        try:
            self._state_avals = shape_struct_tree(state)
        except Exception:  # graftlint: disable=ROB001 (MFU is best-effort; _mfu_broken records the degradation)
            self._state_avals = None
            # trainer main thread only — serving threads never touch the
            # MFU machinery, so the health lock is not required here
            self._mfu_broken = True  # graftlint: disable=LCK001 (trainer main thread only)

    # -- resilience health events --------------------------------------------

    def health(self, kind: str, **fields) -> None:
        """Record one resilience health event (docs/TELEMETRY.md schema):
        counted always (the manifest's ``health`` tally is how tests and
        teleview see a disabled-sink run's events too), emitted to the
        sinks when any exist.  ``count=`` in fields bumps the tally by more
        than one (e.g. K skipped steps in one scanned dispatch)."""
        n = int(fields.pop("count", 1))
        with self._health_lock:
            # the emit rides the same lock: serving calls health() from
            # concurrent handler threads, and the JSONL sink's shared
            # text stream is not thread-safe — unlocked writes could
            # interleave into garbled lines
            self._health_counts[kind] = self._health_counts.get(kind, 0) + n
            self._emit({
                "event": "health",
                "kind": kind,
                "count": n,
                "run_id": self.run_id,
                "rank": self.rank,
                "t": time.time(),
                **fields,
            })

    @property
    def health_counts(self) -> Dict[str, int]:
        with self._health_lock:
            return dict(self._health_counts)

    # -- serving step records ------------------------------------------------

    def serve_step(self, *, bucket: Dict[str, int], num_graphs: int,
                   nodes_real: float, edges_real: float, predict_ms: float,
                   wait_ms: float, reason: str, fill_pct: float,
                   demand: int = 0, max_nodes_per_graph: int = 0,
                   max_edges_per_graph: int = 0,
                   ladder: Optional[Sequence[int]] = None) -> None:
        """One per-flush serving step record in the SAME JSONL step
        schema the trainer emits (``event: "step"`` with the ``padding``
        sub-record of flush_steps) so tools/teleview.py and the bucket
        autotuner (serve/autotune.py, tools/buckettune.py) read one
        format for train and serve padding waste alike.  Serve records
        carry ``source: "serve"`` plus the chosen ``bucket``
        (graph/node/edge capacities) and the flush's ladder-independent
        ``demand`` (autotune.required_capacity).

        ``bucket`` is ``{"graphs": real capacity, "nodes": padded node
        slots, "edges": padded edge slots}`` — the cache_stats bucket
        rendering.  Rides the health lock: the JSONL sink's stream is
        shared with concurrent handler threads' health events."""
        if not self.enabled:
            return
        predict_s = max(float(predict_ms), 1e-6) / 1e3
        padded_nodes = int(bucket["nodes"])
        padded_edges = int(bucket["edges"])
        padded_graphs = int(bucket["graphs"]) + 1  # + the padding graph
        rec: Dict[str, Any] = {
            "event": "step",
            "source": "serve",
            "run_id": self.run_id,
            "rank": self.rank,
            "t": time.time(),
            "step": 0,  # filled under the lock below
            "num_graphs": float(num_graphs),
            "step_time_s": predict_s,
            "graphs_per_s": float(num_graphs) / predict_s,
            "predict_ms": round(float(predict_ms), 3),
            "wait_ms": round(float(wait_ms), 3),
            "reason": reason,
            "fill_pct": round(float(fill_pct), 2),
            "bucket": dict(bucket),
            "demand": int(demand),
            "max_nodes_per_graph": int(max_nodes_per_graph),
            "max_edges_per_graph": int(max_edges_per_graph),
            # the FULL configured ladder, not just the bucket used:
            # offline tuning (tools/buckettune.py) must see capacities
            # traffic never landed in, or it would shrink the top and
            # start 413-ing requests the live ladder admits
            "ladder": [int(c) for c in (ladder or [])],
            "padding": {
                "nodes_real": float(nodes_real),
                "edges_real": float(edges_real),
                "padded_nodes": padded_nodes,
                "padded_edges": padded_edges,
                "padded_graphs": padded_graphs,
                "nodes_waste_pct": waste_pct(nodes_real, padded_nodes),
                "edges_waste_pct": waste_pct(edges_real, padded_edges),
                "graphs_waste_pct": waste_pct(num_graphs, padded_graphs),
            },
        }
        with self._health_lock:
            self._serve_steps += 1
            rec["step"] = self._serve_steps
            self.ring.push({k: v for k, v in rec.items()
                            if isinstance(v, (int, float))
                            and not isinstance(v, bool)})
            self._emit(rec)

    # -- sharding block (ZeRO, docs/SCALING.md §4) ---------------------------

    def log_sharding(self, info: Dict[str, Any]) -> None:
        """Record the run's parameter/optimizer-state sharding layout
        (zero_stage requested + effective, axis size, per-device resident
        bytes, padded-slice waste, fallback reason).  Stored ALWAYS — the
        end-of-run manifest carries it even for sink-less ranks — and
        emitted as a ``sharding`` event when the subsystem is on, so
        tools/teleview.py can warn when ZeRO was requested but the run
        fell back to replicated."""
        self._sharding = dict(info)
        if self.enabled:
            self._emit({
                "event": "sharding",
                "run_id": self.run_id,
                "rank": self.rank,
                "t": time.time(),
                **self._sharding,
            })

    def log_comms(self, split: Dict[str, Any]) -> None:
        """Record the comm-vs-compute split the opt-in A/B probe measured
        (telemetry/comms.py): per mesh path, full-step ms vs collective-only
        ms and the derived comm %.  Stored always (manifest ``comms``
        block), emitted as a ``comms`` event when the subsystem is on."""
        self._comms = dict(split)
        if self.enabled:
            self._emit({
                "event": "comms",
                "run_id": self.run_id,
                "rank": self.rank,
                "t": time.time(),
                **self._comms,
            })

    def log_program_memory(self, name: str, **fields: int) -> None:
        """One step program's ``memory_analysis()``, per device, as the
        compiler states it (telemetry/hlo_scopes.py StepPrograms.write):
        a ``program_memory`` event."""
        if self.enabled:
            self.emit_threadsafe({"event": "program_memory", "name": name,
                                  **fields})

    def resume_counts(self, global_step: int) -> None:
        """Continue the step/dispatch numbering of a preempted run so the
        resumed JSONL stream's ``step`` axis doesn't restart at zero."""
        # trainer main thread only (resume happens before any serving
        # thread exists); the step counters are never shared cross-thread
        self._global_step = max(0, int(global_step))  # graftlint: disable=LCK001 (trainer main thread only)
        self._steps_dispatched = self._global_step  # graftlint: disable=LCK001 (trainer main thread only)
        self._dispatch = self._global_step // max(1, self._steps_per_item)  # graftlint: disable=LCK001 (trainer main thread only)

    # -- per-step path (zero-sync) -------------------------------------------

    def begin_epoch(self, epoch: int) -> None:
        self._epoch = int(epoch)
        self._in_epochs = True
        self._epoch_t0 = time.perf_counter()

    def position(self) -> Tuple[Optional[int], Optional[int]]:
        """(epoch, optimizer steps dispatched so far) for a record made
        beside the step path (telemetry/programs.py), from any thread;
        (None, None) until the trainer begins its first epoch."""
        if not self._in_epochs:
            return None, None
        return self._epoch, self._steps_dispatched

    def on_step(self, metrics, batch) -> None:
        """Record one dispatched train step: device metric scalars + host
        timestamp + static batch metadata.  No device sync."""
        if not self.enabled:
            return
        sig = (tuple(batch.x.shape), tuple(batch.senders.shape),
               tuple(batch.graph_mask.shape))
        if (self._step_fn is not None and sig not in self._flops_cache
                and not self._mfu_broken):
            # first sighting of this PadSpec bucket: stash avals now (cheap)
            # so flush can compile the cost analysis off the hot path
            self._flops_cache[sig] = None
            self._pending_avals[sig] = shape_struct_tree(batch)
        self._steps_dispatched += self._steps_per_item  # graftlint: disable=LCK001 (trainer main thread only)
        self._pending.append(
            (metrics, batch_pad_meta(batch), time.perf_counter(), sig))

    def _flops_for(self, sig: tuple) -> Optional[float]:
        if self._mfu_broken or self._step_fn is None:
            return None
        cached = self._flops_cache.get(sig)
        if cached is not None:
            return cached
        avals = self._pending_avals.get(sig)
        if avals is None or self._state_avals is None:
            return None
        try:
            # a second compile of the step, for XLA's cost model only
            with tracer.timer("setup.mfu_cost"):
                fl = step_cost_flops(
                    self._step_fn, self._state_avals, avals)
            self._flops_cache[sig] = fl
            return fl
        except Exception:  # graftlint: disable=ROB001 (cost analysis is best-effort; _mfu_broken records it)
            # (e.g. a backend without cost_analysis); disable for the run
            self._mfu_broken = True  # graftlint: disable=LCK001 (trainer main thread only)
            return None

    def flush_steps(self) -> None:
        """One ``device_get`` of every pending step's metric scalars, then
        emit the step records.  Called at epoch end by the trainer, after
        its own combined accumulator fetch."""
        if not self.enabled or not self._pending:
            self._pending = []
            return
        import jax

        fetched = jax.device_get([m for m, _, _, _ in self._pending])
        prev_t = self._epoch_t0
        for (_, pad, t, sig), m in zip(self._pending, fetched):
            dt = max(t - prev_t, 0.0)
            prev_t = t
            n_tasks = sum(1 for k in m if k.startswith("task_"))
            ng = float(m.get("num_graphs", 0.0))
            nodes_real = float(m.get("nodes_real", 0.0))
            edges_real = float(m.get("edges_real", 0.0))
            self._dispatch += 1  # graftlint: disable=LCK001 (trainer main thread only)
            self._global_step += self._steps_per_item  # graftlint: disable=LCK001 (trainer main thread only)
            rec: Dict[str, Any] = {
                "event": "step",
                "run_id": self.run_id,
                "rank": self.rank,
                "t": time.time(),
                "epoch": self._epoch,
                "step": self._global_step,
                "dispatch": self._dispatch,
                "steps_in_dispatch": self._steps_per_item,
                "loss": float(m["loss"]),
                "tasks": [float(m[f"task_{i}"]) for i in range(n_tasks)],
                "num_graphs": ng,
                "step_time_s": dt,
            }
            for k in ("grad_norm", "param_norm", "update_norm"):
                if k in m:
                    rec[k] = float(m[k])
            if "skipped" in m:
                # non-finite guard: count of suppressed updates in this
                # dispatch (0 or 1 unscanned; 0..K scanned)
                nskip = int(round(float(m["skipped"])))
                rec["skipped"] = nskip
                if nskip > 0:
                    self.health("step_skipped", count=nskip,
                                step=self._global_step, epoch=self._epoch)
            if dt > 0:
                rec["graphs_per_s"] = ng / dt
                rec["nodes_per_s"] = nodes_real / dt
                rec["edges_per_s"] = edges_real / dt
            rec["padding"] = {
                "nodes_real": nodes_real,
                "edges_real": edges_real,
                **pad,
                "nodes_waste_pct": waste_pct(nodes_real, pad["padded_nodes"]),
                "edges_waste_pct": waste_pct(edges_real, pad["padded_edges"]),
                "graphs_waste_pct": waste_pct(ng, pad["padded_graphs"]),
            }
            # what the model counted (telemetry/counters.py)
            rec.update(counters.record_blocks(m))
            fl = self._flops_for(sig)
            if fl:
                rec["flops_per_dispatch"] = fl
                if dt > 0 and self._peak:
                    rec["mfu_est_pct"] = mfu_pct(fl, dt, self._peak)
            self.ring.push({k: v for k, v in rec.items()
                            if isinstance(v, (int, float))})
            self._emit(rec)
        self._pending = []

    # -- per-epoch path ------------------------------------------------------

    def log_epoch(self, epoch: int, scalars: Dict[str, Any],
                  train_loader=None) -> None:
        """Emit the epoch record (all ranks call this; collectives inside).

        ``scalars`` carries train/val/test loss, lr, epoch_time_s,
        train_tasks.  Pipeline counters and loader padding efficiency are
        collected here; cross-rank min/max/avg of timing metrics ride the
        host collectives when enabled.
        """
        rec: Dict[str, Any] = {
            "event": "epoch",
            "run_id": self.run_id,
            "rank": self.rank,
            "t": time.time(),
            "epoch": int(epoch),
            **scalars,
        }
        if self.enabled:
            if train_loader is not None:
                eff = _loader_padding_efficiency(train_loader)
                if eff is not None:
                    rec["padding_efficiency"] = eff
                    rec["padding_waste_pct"] = (1.0 - eff) * 100.0
            pipe = pipeline.snapshot(reset=True)
            if pipe:
                rec["pipeline"] = pipe
        # collectives only when the subsystem is ON: a disabled logger must
        # not add a per-epoch host collective to every multi-process run
        if self.enabled and self.cross_rank and self.world_size > 1:
            self._reduce_ranks(rec)
        self._emit(rec)

    def _reduce_ranks(self, rec: Dict[str, Any]) -> None:
        """min/max/avg of per-rank timing metrics via host collectives.
        The key list is derived the same way on every rank (same code, same
        trainer-built record), keeping the collective symmetric."""
        from hydragnn_tpu.parallel.comm import host_allreduce

        keys = [k for k in ("epoch_time_s", "graphs_per_s") if k in rec]
        if not keys:
            return
        vals = np.asarray([float(rec[k]) for k in keys], np.float64)
        mn = host_allreduce(vals, "min")
        mx = host_allreduce(vals, "max")
        sm = host_allreduce(vals, "sum")
        rec["ranks"] = {
            k: {"min": float(mn[i]), "max": float(mx[i]),
                "avg": float(sm[i]) / self.world_size}
            for i, k in enumerate(keys)
        }

    # -- end of run ----------------------------------------------------------

    def finalize(self, history: Optional[Dict[str, Any]] = None,
                 timers: Optional[Dict[str, Any]] = None) -> None:
        """Write the end-of-run manifest (TimerTracer summaries folded in)
        and close the sinks."""
        if self.enabled:
            rec: Dict[str, Any] = {
                "event": "manifest",
                "run_id": self.run_id,
                "run_name": self.run_name,
                "rank": self.rank,
                "world_size": self.world_size,
                "t": time.time(),
                "total_steps": self._global_step,
                "total_dispatches": self._dispatch,
                **self._device,
                "peak_flops_basis": self._peak,
                "flops_method": "XLA cost model of the timed program "
                                "(telemetry/flops.py:step_cost_flops — "
                                "shared with bench.py; Pallas-opaque)",
                "ring_summary": self.ring.aggregate(),
            }
            if history is not None:
                rec["history"] = {
                    k: v for k, v in history.items()
                    if k in ("train", "val", "test", "lr", "epoch_time",
                             "pipeline")}
            if timers is not None:
                rec["timers"] = timers
            if self._health_counts:
                rec["health"] = dict(self._health_counts)
            if self._sharding is not None:
                rec["sharding"] = dict(self._sharding)
            if self._comms is not None:
                rec["comms"] = dict(self._comms)
            if self.spans is not None:
                rec["spans"] = self.spans.summary()
            # fused-vs-fallback dispatch tally (this run's delta over the
            # process-cumulative trace-time counts): a run that silently
            # fell off the fast path shows ``<op>:scatter`` entries here
            # and in tools/teleview.py
            delta = pipeline.dispatch_delta(
                self._dispatch_base, pipeline.dispatch_snapshot())
            if delta:
                rec["aggr_dispatch"] = delta
                rec["aggr_dispatch_summary"] = pipeline.dispatch_summary(
                    delta)
            pipe = pipeline.snapshot(reset=True)
            if pipe:
                rec["pipeline"] = pipe
            self._emit(rec)
            pipeline.set_enabled(False)
        programs.RECORDER.detach(self)
        for s in self.sinks:
            try:
                s.close()
            except Exception:  # graftlint: disable=ROB001 (sink close is best-effort at shutdown)
                pass
        self.sinks = []

    # -- internals -----------------------------------------------------------

    def _emit(self, record: Dict[str, Any]) -> None:
        for s in self.sinks:
            s.emit(record)

    def emit_threadsafe(self, record: Dict[str, Any]) -> None:
        """The emit hook of what records from any thread (SpanRecorder:
        concurrent serve handler threads; ProgramRecorder: the prefetch
        thread builds too): stamp run identity and ride the health lock,
        because they share the JSONL sink's text stream."""
        record.setdefault("run_id", self.run_id)
        record.setdefault("rank", self.rank)
        record.setdefault("t", time.time())
        with self._health_lock:
            self._emit(record)

    @property
    def jsonl_path(self) -> str:
        return os.path.join(self.out_dir, "events.jsonl")
