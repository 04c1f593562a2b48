"""The cross-artifact registries: env knobs and health-event kinds.

These are the single declared sources the registry rules check code and
docs against:

- every ``HYDRAGNN_*`` string in code must name a knob declared in
  :data:`KNOBS` (rule REG001), every declared knob must still be read
  somewhere and appear in docs/KNOBS.md (REG002), and docs/KNOBS.md is
  GENERATED from this table (``tools/graftlint.py --emit-docs``) so it
  cannot drift;
- every literal ``MetricsLogger.health(kind=...)`` emitted by the
  package must name a kind declared in :data:`HEALTH_KINDS` (REG003),
  and every declared kind must be emitted somewhere and documented in
  docs/TELEMETRY.md (REG004);
- every literal name passed to the trace API must be declared (REG006):
  spans and host regions (``span``, ``record_interval``, ``utils/tracer``
  ``start``/``stop``/``timer``/``profile``) in :data:`SPAN_NAMES`,
  device scopes (``phase``, ``comm_region``) in :data:`SCOPE_NAMES`,
  kernels (``pallas_call(name=...)``) in :data:`KERNEL_NAMES` — the
  flight recorder's percentile views and the benchmark's trace readers
  group by these names, so an undeclared ad-hoc name is one nobody's
  tables will ever aggregate.  The three tables of docs/TELEMETRY.md
  "Tracing" are GENERATED from them (``--emit-docs``).

Adding a knob, health kind, or span therefore means: declare it here,
use it, document it — the lint gate fails on any one of the three
missing.
"""

from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str  # HYDRAGNN_* spelling
    config: str  # config-file spelling ("" = env-only knob)
    default: str  # effective default, as documented
    module: str  # owning module (repo-relative)
    desc: str  # one-line effect


def _k(name, config, default, module, desc):
    return Knob(name=name, config=config, default=default, module=module,
                desc=desc)


_KNOB_LIST = [
    # -- data pipeline ----------------------------------------------------
    _k("HYDRAGNN_NUM_WORKERS", "", "2",
       "hydragnn_tpu/data/prefetch.py",
       "prefetch worker thread count (dataloader auto-pipeline may set it)"),
    _k("HYDRAGNN_COLLATE_PROCS", "", "4",
       "hydragnn_tpu/data/prefetch.py",
       "collate process-pool size (0 = in-thread collation)"),
    _k("HYDRAGNN_COLLATE_SHM", "", "1",
       "hydragnn_tpu/data/prefetch.py",
       "ship collated batches via shared memory (0 = pickle over pipe)"),
    _k("HYDRAGNN_AFFINITY", "", "0",
       "hydragnn_tpu/data/prefetch.py",
       "pin prefetch/collate workers to CPU cores"),
    _k("HYDRAGNN_AFFINITY_WIDTH", "", "2",
       "hydragnn_tpu/data/prefetch.py",
       "cores per pinned worker"),
    _k("HYDRAGNN_AFFINITY_OFFSET", "", "0",
       "hydragnn_tpu/data/prefetch.py",
       "first core index for worker pinning"),
    _k("HYDRAGNN_NUM_BUCKETS", "", "0 (auto)",
       "hydragnn_tpu/data/dataloader.py",
       "PadSpec bucket-ladder size for the training loader"),
    _k("HYDRAGNN_USE_VARIABLE_GRAPH_SIZE", "", "0",
       "hydragnn_tpu/data/dataloader.py",
       "legacy spelling: 4-bucket ladder for variable-size datasets"),
    _k("HYDRAGNN_RESIDENT_DATASET", "", "auto",
       "hydragnn_tpu/train/trainer.py",
       "keep collated batches device-resident across epochs"),
    _k("HYDRAGNN_RESIDENT_BUDGET_MB", "", "6144",
       "hydragnn_tpu/train/trainer.py",
       "HBM budget the auto-pipeline sizes the resident set against"),
    _k("HYDRAGNN_DEVICE_PREFETCH", "", "0",
       "hydragnn_tpu/train/trainer.py",
       "overlap H2D transfer one batch ahead"),
    # -- streaming data plane (data/stream/) ------------------------------
    _k("HYDRAGNN_STREAM", "Dataset.stream", "0",
       "hydragnn_tpu/data/stream/config.py",
       "stream gpack samples with bounded residency instead of loading "
       "the dataset in memory"),
    _k("HYDRAGNN_STREAM_PATH", "Dataset.stream_path", "",
       "hydragnn_tpu/data/stream/config.py",
       "gpack store path the streaming loader reads from"),
    _k("HYDRAGNN_STREAM_WINDOW", "Dataset.stream_window", "1024",
       "hydragnn_tpu/data/stream/config.py",
       "decoded-sample residency window W (peak ~ W + batch_size samples)"),
    _k("HYDRAGNN_STREAM_ORDER", "Dataset.stream_order", "global",
       "hydragnn_tpu/data/stream/config.py",
       "epoch order: global (bit-parity with in-memory) | sequential | "
       "block (locality shuffle)"),
    _k("HYDRAGNN_STREAM_BLOCK", "Dataset.stream_block", "2048",
       "hydragnn_tpu/data/stream/config.py",
       "block size for stream_order=block"),
    _k("HYDRAGNN_STREAM_TAIL", "Dataset.stream_tail", "",
       "hydragnn_tpu/data/stream/config.py",
       "ingest dir to tail: re-reads the manifest each epoch and trains "
       "on newly sealed segments (implies stream)"),
    _k("HYDRAGNN_STREAM_OPEN_RETRIES", "Dataset.stream_open_retries", "2",
       "hydragnn_tpu/data/stream/config.py",
       "store-open retry attempts (bounded backoff) before the "
       "in-memory fallback"),
    # -- trainer / pipeline ----------------------------------------------
    _k("HYDRAGNN_AUTO_PIPELINE", "", "1",
       "hydragnn_tpu/train/trainer.py",
       "derive pipeline knobs (scan K, resident, workers) automatically"),
    _k("HYDRAGNN_STEPS_PER_DISPATCH", "", "auto",
       "hydragnn_tpu/train/trainer.py",
       "optimizer steps folded into one scanned dispatch"),
    _k("HYDRAGNN_MAX_NUM_BATCH", "", "0 (all)",
       "hydragnn_tpu/train/trainer.py",
       "truncate each epoch to N batches (smoke runs)"),
    _k("HYDRAGNN_VALTEST", "", "1",
       "hydragnn_tpu/train/trainer.py",
       "run the val/test phases each epoch (0 = train only)"),
    _k("HYDRAGNN_DUMP_TESTDATA", "", "0",
       "hydragnn_tpu/train/trainer.py",
       "dump per-sample test predictions for postprocessing"),
    _k("HYDRAGNN_NUM_SLICES", "", "0",
       "hydragnn_tpu/train/trainer.py",
       "force a (dcn, ici) multi-slice mesh shape"),
    _k("HYDRAGNN_BN_MOMENTUM", "", "model default",
       "hydragnn_tpu/models/layers.py",
       "BatchNorm momentum override"),
    _k("HYDRAGNN_TRAIN_DTYPE", "Training.train_dtype_policy", "f32",
       "hydragnn_tpu/train/trainer.py",
       "train-step compute dtype: f32 | bf16 (f32 master state; "
       "step-0 golden gate, loud f32 fallback)"),
    # -- parallel / distributed ------------------------------------------
    _k("HYDRAGNN_MASTER_ADDR", "", "127.0.0.1",
       "hydragnn_tpu/parallel/mesh.py",
       "jax.distributed coordinator address"),
    _k("HYDRAGNN_MASTER_PORT", "", "8889",
       "hydragnn_tpu/parallel/mesh.py",
       "jax.distributed coordinator port"),
    _k("HYDRAGNN_ZERO", "Training.zero_stage", "0",
       "hydragnn_tpu/parallel/zero.py",
       "ZeRO stage (0|1|2); env wins over the config stage"),
    _k("HYDRAGNN_GRAPH_SHARD", "Training.graph_shard", "off",
       "hydragnn_tpu/graph/partition.py",
       "graph-sharding backend: off | halo (production) | gspmd (baseline)"),
    _k("HYDRAGNN_GRAPH_SHARD_METHOD", "Training.graph_shard_method", "sfc",
       "hydragnn_tpu/graph/partition.py",
       "partition node order: sfc (Morton) | bfs | block"),
    _k("HYDRAGNN_GRAPH_SHARD_HOPS", "Training.graph_shard_hops",
       "0 (num_conv_layers)", "hydragnn_tpu/graph/partition.py",
       "halo depth in hops (0 = the model's conv depth)"),
    _k("HYDRAGNN_GRAPH_SHARD_HALO_MAX", "Training.graph_shard_halo_max",
       "0 (auto bucket)", "hydragnn_tpu/graph/partition.py",
       "per-peer halo row cap; exceeding it raises (never truncates)"),
    # -- kernels / fused-path gates --------------------------------------
    _k("HYDRAGNN_AGGR_BACKEND", "", "scatter",
       "hydragnn_tpu/ops/aggregate.py",
       "aggregation backend, one of two: scatter (XLA) | fused (Pallas "
       "where collate's marker and the widths allow)"),
    _k("HYDRAGNN_GAT_FUSED", "", "auto",
       "hydragnn_tpu/models/gat.py",
       "GAT fused edge-attention gate"),
    _k("HYDRAGNN_EGCL_FUSED", "", "auto",
       "hydragnn_tpu/models/egnn.py",
       "EGNN fused EGCL interaction-block gate (1/0 forces, subject "
       "to the kernel's structural width limits)"),
    _k("HYDRAGNN_CGCNN_FUSED", "", "auto",
       "hydragnn_tpu/models/cgcnn.py",
       "CGCNN fused gated-sum block gate (1/0 forces, subject to the "
       "kernel's structural width limits)"),
    _k("HYDRAGNN_DN_TRI_OFF", "", "0",
       "hydragnn_tpu/models/dimenet.py",
       "disable the DimeNet fused-triplet kernel"),
    _k("HYDRAGNN_DIMENET_FUSED_TRI", "", "0",
       "hydragnn_tpu/models/dimenet.py",
       "force the fused-triplet kernel past the dataset-bound gate"),
    _k("HYDRAGNN_DN_ROW_MLP_OFF", "", "0",
       "hydragnn_tpu/models/dimenet.py",
       "disable the fused residual-MLP tail"),
    # -- telemetry --------------------------------------------------------
    _k("HYDRAGNN_TELEMETRY", "Telemetry.enable", "0",
       "hydragnn_tpu/telemetry/logger.py",
       "enable the telemetry subsystem"),
    _k("HYDRAGNN_TELEMETRY_SINKS", "Telemetry.sinks", "jsonl,stdout",
       "hydragnn_tpu/telemetry/logger.py",
       "comma list of sinks (jsonl,csv,stdout,tensorboard)"),
    _k("HYDRAGNN_TELEMETRY_DIR", "Telemetry.dir",
       "logs/<run>/telemetry", "hydragnn_tpu/telemetry/logger.py",
       "telemetry output directory"),
    _k("HYDRAGNN_TELEMETRY_HEARTBEAT", "Telemetry.heartbeat", "50",
       "hydragnn_tpu/telemetry/logger.py",
       "stdout heartbeat cadence (steps)"),
    _k("HYDRAGNN_TRACE", "Telemetry.trace", "0",
       "hydragnn_tpu/telemetry/trace.py",
       "flight recorder: record request/train-phase spans (JSONL "
       "event=span; adds one device sync per traced train step)"),
    _k("HYDRAGNN_TRACE_RING", "Telemetry.trace_ring", "512",
       "hydragnn_tpu/telemetry/trace.py",
       "in-memory span ring capacity (JSONL stream is unbounded)"),
    _k("HYDRAGNN_COMMS_PROBE", "", "0",
       "hydragnn_tpu/telemetry/comms.py",
       "A/B comm-vs-compute probe at train start (mesh DP path); split "
       "lands in the manifest `comms` block"),
    _k("HYDRAGNN_SLO_P99_MS", "", "0 (off)",
       "hydragnn_tpu/telemetry/slo.py",
       "serving SLO: p99 latency target the burn-rate monitor checks"),
    _k("HYDRAGNN_SLO_SHED_BUDGET", "", "0.05",
       "hydragnn_tpu/telemetry/slo.py",
       "serving SLO: tolerated shed/error ratio (fraction of requests)"),
    _k("HYDRAGNN_SLO_WINDOW_S", "", "60",
       "hydragnn_tpu/telemetry/slo.py",
       "burn-rate monitor sliding-window length"),
    _k("HYDRAGNN_SLO_BURN", "", "2.0",
       "hydragnn_tpu/telemetry/slo.py",
       "burn-rate multiple of the shed budget that fires `slo_burn`"),
    # -- profiler (utils/profile.py env overlay) --------------------------
    _k("HYDRAGNN_PROFILE", "Profile.enable", "0",
       "hydragnn_tpu/utils/profile.py",
       "capture a jax.profiler device trace on the step schedule"),
    _k("HYDRAGNN_PROFILE_WAIT", "Profile.wait", "5",
       "hydragnn_tpu/utils/profile.py",
       "profiler schedule: steps to skip before warmup"),
    _k("HYDRAGNN_PROFILE_WARMUP", "Profile.warmup", "3",
       "hydragnn_tpu/utils/profile.py",
       "profiler schedule: warmup steps before the trace starts"),
    _k("HYDRAGNN_PROFILE_ACTIVE", "Profile.active", "3",
       "hydragnn_tpu/utils/profile.py",
       "profiler schedule: traced steps"),
    _k("HYDRAGNN_PROFILE_DIR", "Profile.trace_dir",
       "logs/<run>/trace", "hydragnn_tpu/utils/profile.py",
       "device-trace output directory"),
    # -- resilience (Training section) -----------------------------------
    _k("HYDRAGNN_NONFINITE_GUARD", "Training.nonfinite_guard", "0",
       "hydragnn_tpu/resilience/config.py",
       "in-jit non-finite step guard (skip bad steps)"),
    _k("HYDRAGNN_GUARD_MAX_BAD", "Training.guard_max_consecutive", "5",
       "hydragnn_tpu/resilience/config.py",
       "consecutive skipped steps before NonFiniteTrainingError"),
    _k("HYDRAGNN_GUARD_POLL", "Training.guard_poll_every", "8",
       "hydragnn_tpu/resilience/config.py",
       "guard-monitor poll cadence (batches)"),
    _k("HYDRAGNN_PREEMPT", "Training.preemption", "1",
       "hydragnn_tpu/resilience/config.py",
       "SIGTERM/SIGINT preemption-aware checkpointing"),
    _k("HYDRAGNN_PREEMPT_SYNC", "Training.preempt_sync_every", "8",
       "hydragnn_tpu/resilience/config.py",
       "multi-host preemption-agreement cadence (polls)"),
    _k("HYDRAGNN_CKPT_RETRIES", "Training.ckpt_retries", "3",
       "hydragnn_tpu/resilience/config.py",
       "checkpoint-write retry attempts"),
    _k("HYDRAGNN_CKPT_BACKOFF", "Training.ckpt_backoff", "0.5",
       "hydragnn_tpu/resilience/config.py",
       "checkpoint retry backoff (seconds, doubling)"),
    _k("HYDRAGNN_ELASTIC_RESUME", "Training.elastic_resume", "strict",
       "hydragnn_tpu/resilience/elastic.py",
       "world-shape-mismatch resume policy: strict refuses loudly, "
       "epoch admits the resize at an epoch boundary"),
    # -- chaos (test-only fault injection) -------------------------------
    _k("HYDRAGNN_CHAOS_NAN_STEP", "Training.Chaos.nan_step", "off",
       "hydragnn_tpu/resilience/chaos.py",
       "inject NaN loss at step spec k|k1,k2|k+"),
    _k("HYDRAGNN_CHAOS_PREEMPT_STEP", "Training.Chaos.preempt_step", "off",
       "hydragnn_tpu/resilience/chaos.py",
       "inject a preemption signal at step k"),
    _k("HYDRAGNN_CHAOS_CKPT_FAILS", "Training.Chaos.ckpt_fails", "off",
       "hydragnn_tpu/resilience/chaos.py",
       "fail the first N checkpoint writes"),
    _k("HYDRAGNN_CHAOS_ELASTIC", "Training.Chaos.elastic", "off",
       "hydragnn_tpu/resilience/chaos.py",
       "force an elastic resize of ±k hosts at an epoch boundary "
       "(epoch:±k | e:±k)"),
    _k("HYDRAGNN_CHAOS_SERVE_PREDICT_MS", "Serving.Chaos.predict_ms",
       "off", "hydragnn_tpu/resilience/chaos.py",
       "inject predict latency (ms|ms@k+)"),
    _k("HYDRAGNN_CHAOS_SERVE_FAIL_STEP", "Serving.Chaos.fail_step", "off",
       "hydragnn_tpu/resilience/chaos.py",
       "fail predict flushes at flush spec k|k1,k2|k+"),
    _k("HYDRAGNN_CHAOS_SERVE_RELOAD_CORRUPT",
       "Serving.Chaos.reload_corrupt", "off",
       "hydragnn_tpu/resilience/chaos.py",
       "NaN-corrupt the next N reload candidates"),
    _k("HYDRAGNN_CHAOS_REPLICA_KILL", "Serving.FleetChaos.kill", "off",
       "hydragnn_tpu/resilience/chaos.py",
       "kill replica at probe tick spec tick[:replica]|tick+"),
    _k("HYDRAGNN_CHAOS_REPLICA_HANG", "Serving.FleetChaos.hang", "off",
       "hydragnn_tpu/resilience/chaos.py",
       "wedge a replica's predict at probe tick spec"),
    _k("HYDRAGNN_CHAOS_REPLICA_FLAP", "Serving.FleetChaos.flap", "off",
       "hydragnn_tpu/resilience/chaos.py",
       "kill the target at EVERY armed tick (crash loop)"),
    _k("HYDRAGNN_CHAOS_TENANT_HOT", "Serving.FleetChaos.tenant_hot", "off",
       "hydragnn_tpu/resilience/chaos.py",
       "mark a tenant hot at probe tick spec tick[:tenant]|tick+ "
       "(router sheds it 429)"),
    _k("HYDRAGNN_CHAOS_SCALE_FAIL", "Serving.FleetChaos.scale_fail", "off",
       "hydragnn_tpu/resilience/chaos.py",
       "make the next scale-up's fresh replica die at probe tick spec"),
    # -- serving ----------------------------------------------------------
    _k("HYDRAGNN_SERVE_BUCKETS", "Serving.buckets", "1,4,16",
       "hydragnn_tpu/serve/config.py",
       "batch-capacity bucket ladder (comma list, ascending)"),
    _k("HYDRAGNN_SERVE_MAX_NODES", "Serving.max_nodes_per_graph", "0",
       "hydragnn_tpu/serve/config.py",
       "per-graph worst-case nodes (sizes bucket PadSpecs)"),
    _k("HYDRAGNN_SERVE_MAX_EDGES", "Serving.max_edges_per_graph", "0",
       "hydragnn_tpu/serve/config.py",
       "per-graph worst-case edges (sizes bucket PadSpecs)"),
    _k("HYDRAGNN_SERVE_EDGE_NORM", "Serving.edge_length_norm", "0.0",
       "hydragnn_tpu/serve/config.py",
       "edge-length normalization constant (training provenance)"),
    _k("HYDRAGNN_SERVE_MAX_WAIT_MS", "Serving.max_wait_ms", "20",
       "hydragnn_tpu/serve/config.py",
       "micro-batcher deadline-flush budget"),
    _k("HYDRAGNN_SERVE_QUEUE", "Serving.max_queue", "1024",
       "hydragnn_tpu/serve/config.py",
       "bounded request-queue capacity"),
    _k("HYDRAGNN_SERVE_HOST", "Serving.host", "127.0.0.1",
       "hydragnn_tpu/serve/config.py", "HTTP bind host"),
    _k("HYDRAGNN_SERVE_PORT", "Serving.port", "8808",
       "hydragnn_tpu/serve/config.py", "HTTP bind port (0 = ephemeral)"),
    _k("HYDRAGNN_SERVE_DRAIN_S", "Serving.drain_timeout_s", "10",
       "hydragnn_tpu/serve/config.py",
       "graceful-shutdown queue-drain budget"),
    _k("HYDRAGNN_SERVE_DEADLINE_MS", "Serving.request_deadline_ms",
       "10000", "hydragnn_tpu/serve/config.py",
       "default per-request deadline (queue wait + service)"),
    _k("HYDRAGNN_SERVE_PREDICT_TIMEOUT_S", "Serving.predict_timeout_s",
       "30", "hydragnn_tpu/serve/config.py",
       "predict watchdog (flush exceeding it fails, 504)"),
    _k("HYDRAGNN_SERVE_BREAKER_THRESHOLD", "Serving.breaker_threshold",
       "5", "hydragnn_tpu/serve/config.py",
       "consecutive flush failures that trip the breaker (0 = off)"),
    _k("HYDRAGNN_SERVE_BREAKER_COOLDOWN_S", "Serving.breaker_cooldown_s",
       "5", "hydragnn_tpu/serve/config.py",
       "breaker open -> half-open probe delay"),
    _k("HYDRAGNN_SERVE_RELOAD_WATCH", "Serving.reload_watch_path", "",
       "hydragnn_tpu/serve/config.py",
       "checkpoint file to hot-reload on mtime change"),
    _k("HYDRAGNN_SERVE_RELOAD_WATCH_S", "Serving.reload_watch_s", "0",
       "hydragnn_tpu/serve/config.py",
       "reload-watch poll interval (0 = off)"),
    _k("HYDRAGNN_SERVE_RELOAD_ROOT", "Serving.reload_root", "",
       "hydragnn_tpu/serve/config.py",
       "allowlisted checkpoint dir for non-loopback POST /reload"),
    _k("HYDRAGNN_SERVE_QUANT_POLICY", "Serving.quant_policy", "f32",
       "hydragnn_tpu/serve/config.py",
       "inference dtype policy: f32 | bf16 | int8"),
    _k("HYDRAGNN_SERVE_QUANT_TOL", "Serving.quant_tolerance", "0.05",
       "hydragnn_tpu/serve/config.py",
       "max golden-batch drift a quant policy may introduce"),
    _k("HYDRAGNN_SERVE_FLEET", "Serving.fleet_replicas", "0",
       "hydragnn_tpu/serve/config.py",
       "replica count behind the failover router (0 = single server)"),
    _k("HYDRAGNN_SERVE_FLEET_INPROCESS", "Serving.fleet_inprocess", "0",
       "hydragnn_tpu/serve/config.py",
       "thread replicas in-process (shared compile cache)"),
    _k("HYDRAGNN_SERVE_FLEET_PROBE_S", "Serving.fleet_probe_s", "1",
       "hydragnn_tpu/serve/config.py",
       "supervisor health-probe interval"),
    _k("HYDRAGNN_SERVE_FLEET_BACKOFF_S",
       "Serving.fleet_restart_backoff_s", "1",
       "hydragnn_tpu/serve/config.py",
       "replica restart backoff base (doubles per restart)"),
    _k("HYDRAGNN_SERVE_FLEET_BACKOFF_MAX_S",
       "Serving.fleet_restart_backoff_max_s", "30",
       "hydragnn_tpu/serve/config.py", "replica restart backoff cap"),
    _k("HYDRAGNN_SERVE_FLEET_MAX_RESTARTS", "Serving.fleet_max_restarts",
       "5", "hydragnn_tpu/serve/config.py",
       "restart-storm cap per window (exceeded -> FAILED)"),
    _k("HYDRAGNN_SERVE_FLEET_RESTART_WINDOW_S",
       "Serving.fleet_restart_window_s", "300",
       "hydragnn_tpu/serve/config.py", "restart-storm window"),
    _k("HYDRAGNN_SERVE_FLEET_DRAIN_S", "Serving.fleet_drain_timeout_s",
       "10", "hydragnn_tpu/serve/config.py",
       "drain-and-replace in-flight budget"),
    _k("HYDRAGNN_SERVE_FLEET_STARTUP_S",
       "Serving.fleet_startup_timeout_s", "300",
       "hydragnn_tpu/serve/config.py",
       "subprocess replica first-/healthz budget"),
    _k("HYDRAGNN_SERVE_FLEET_QUORUM", "Serving.fleet_quorum",
       "0 (majority)", "hydragnn_tpu/serve/config.py",
       "live replicas below this -> fleet_degraded"),
    _k("HYDRAGNN_SERVE_FLEET_MIN", "Serving.fleet_min_replicas", "1",
       "hydragnn_tpu/serve/config.py",
       "autoscaler floor: scale-down never goes below this"),
    _k("HYDRAGNN_SERVE_FLEET_MAX", "Serving.fleet_max_replicas", "0",
       "hydragnn_tpu/serve/config.py",
       "autoscaler ceiling (0 = closed-loop autoscaling off)"),
    _k("HYDRAGNN_SERVE_AUTOSCALE_UP_FRAC", "Serving.autoscale_up_frac",
       "0.5", "hydragnn_tpu/serve/config.py",
       "scale up when est queue wait exceeds this fraction of the "
       "request deadline"),
    _k("HYDRAGNN_SERVE_AUTOSCALE_UP_TICKS", "Serving.autoscale_up_ticks",
       "3", "hydragnn_tpu/serve/config.py",
       "consecutive hot probe ticks before a scale-up (hysteresis)"),
    _k("HYDRAGNN_SERVE_AUTOSCALE_QUIET_S", "Serving.autoscale_quiet_s",
       "60", "hydragnn_tpu/serve/config.py",
       "sustained empty-queue window before a zero-drop scale-down"),
    _k("HYDRAGNN_SERVE_AUTOSCALE_COOLDOWN_S",
       "Serving.autoscale_cooldown_s", "30",
       "hydragnn_tpu/serve/config.py",
       "minimum spacing between scale decisions"),
    _k("HYDRAGNN_SERVE_MAX_TENANTS", "Serving.max_tenants", "4",
       "hydragnn_tpu/serve/config.py",
       "resident tenant engines per replica incl. default (LRU beyond)"),
    _k("HYDRAGNN_SERVE_TENANT_BUDGET_FRAC", "Serving.tenant_budget_frac",
       "0", "hydragnn_tpu/serve/config.py",
       "per-tenant outstanding cap as a fraction of fleet drain "
       "capacity (0 = budgets off)"),
    _k("HYDRAGNN_SERVE_MAX_EXECUTABLES", "Serving.max_resident_executables",
       "0", "hydragnn_tpu/serve/config.py",
       "engine AOT-executable LRU cap (0 = unbounded)"),
    # -- misc -------------------------------------------------------------
    _k("HYDRAGNN_SYSTEM", "", "",
       "hydragnn_tpu/hpo.py",
       "HPC system name for HPO launch templates"),
    _k("HYDRAGNN_TEST_SCRATCH", "", "/tmp/hydragnn_tpu_tests",
       "tests/conftest.py", "test scratch directory"),
]

KNOBS: Dict[str, Knob] = {k.name: k for k in _KNOB_LIST}


@dataclasses.dataclass(frozen=True)
class HealthKind:
    name: str
    module: str  # emitting module (repo-relative)
    desc: str


def _h(name, module, desc):
    return HealthKind(name=name, module=module, desc=desc)


_HEALTH_LIST = [
    # training resilience (docs/TELEMETRY.md "health — resilience events")
    _h("step_skipped", "hydragnn_tpu/telemetry/logger.py",
       "in-jit non-finite guard suppressed update(s)"),
    _h("preempt_save", "hydragnn_tpu/train/trainer.py",
       "preemption resume bundle written"),
    _h("walltime_save", "hydragnn_tpu/train/trainer.py",
       "SLURM-walltime resume bundle written"),
    _h("resume_from", "hydragnn_tpu/train/trainer.py",
       "run restored a resume bundle"),
    _h("ckpt_retry", "hydragnn_tpu/resilience/ckpt_io.py",
       "one failed checkpoint-write attempt"),
    _h("ckpt_giveup", "hydragnn_tpu/resilience/ckpt_io.py",
       "checkpoint retries exhausted, run degraded gracefully"),
    _h("nonfinite_abort", "hydragnn_tpu/resilience/guards.py",
       "guard monitor hit N consecutive bad steps and raised"),
    _h("graph_shard_fallback", "hydragnn_tpu/train/trainer.py",
       "graph sharding requested but the run fell back to plain DP"),
    _h("fused_fallback", "hydragnn_tpu/train/trainer.py",
       "an arch fell off its fused edge-block path (structural limit, "
       "missing sender_perm, or env override) and composed the XLA "
       "route instead — fields carry arch and reason"),
    _h("egcl_fallback", "hydragnn_tpu/train/trainer.py",
       "legacy alias of fused_fallback, still emitted when the arch is "
       "EGNN (kept one release for dashboards keyed on the old kind)"),
    _h("train_dtype_reject", "hydragnn_tpu/train/trainer.py",
       "bf16 train policy requested but rejected (golden-gate drift, "
       "graph sharding, or empty loader) — run fell back to f32"),
    # elastic training (docs/TELEMETRY.md + docs/RESILIENCE.md)
    _h("elastic_resize", "hydragnn_tpu/resilience/elastic.py",
       "a world resize was agreed at an epoch boundary, or a "
       "shape-changed resume was admitted"),
    _h("elastic_admit", "hydragnn_tpu/train/trainer.py",
       "this host resumed INTO a new world shape (carries the converted "
       "position and the saved shape)"),
    _h("elastic_retire", "hydragnn_tpu/resilience/elastic.py",
       "world shrinking: surplus hosts exit through the bundle path at "
       "the agreed boundary and never relaunch"),
    _h("elastic_refuse", "hydragnn_tpu/resilience/elastic.py",
       "strict policy refused a world-shape-mismatched resume"),
    # serving lifecycle (docs/TELEMETRY.md "Serving events")
    _h("request_enqueued", "hydragnn_tpu/serve/batcher.py",
       "request accepted into the bounded queue"),
    _h("batch_flushed", "hydragnn_tpu/serve/batcher.py",
       "micro-batcher ran one padded prediction"),
    _h("deadline_flush", "hydragnn_tpu/serve/batcher.py",
       "max_wait_ms fired before a bucket filled"),
    _h("cache_miss", "hydragnn_tpu/serve/engine.py",
       "a request batch compiled at serve time (warmup gap)"),
    _h("batch_error", "hydragnn_tpu/serve/batcher.py",
       "engine failure surfaced to a batch's requests"),
    _h("serve_start", "hydragnn_tpu/serve/server.py",
       "server (or fleet router) came up"),
    _h("serve_drain", "hydragnn_tpu/serve/server.py",
       "graceful drain completed"),
    # overload / robustness (docs/TELEMETRY.md "Overload/robustness kinds")
    _h("request_shed", "hydragnn_tpu/serve/batcher.py",
       "admission control rejected a request before queueing (429)"),
    _h("deadline_expired", "hydragnn_tpu/serve/batcher.py",
       "queued entries whose budget ran out, skipped pre-batch (429)"),
    _h("predict_timeout", "hydragnn_tpu/serve/batcher.py",
       "flush exceeded the predict watchdog (504)"),
    _h("breaker_open", "hydragnn_tpu/resilience/breaker.py",
       "circuit breaker tripped open"),
    _h("breaker_half_open", "hydragnn_tpu/resilience/breaker.py",
       "breaker cooldown elapsed, probe flush armed"),
    _h("breaker_close", "hydragnn_tpu/resilience/breaker.py",
       "probe succeeded, breaker closed"),
    _h("reload_ok", "hydragnn_tpu/serve/engine.py",
       "hot checkpoint reload validated and swapped"),
    _h("reload_rollback", "hydragnn_tpu/serve/engine.py",
       "reload rejected / rolled back (validation, breaker, api)"),
    # quantized inference (docs/TELEMETRY.md "Quantized-inference kinds")
    _h("quant_policy", "hydragnn_tpu/serve/engine.py",
       "non-f32 dtype policy passed the golden gate and serves"),
    _h("quant_reject", "hydragnn_tpu/serve/engine.py",
       "requested policy exceeded quant_tolerance, fell back to f32"),
    # replica fleet (docs/TELEMETRY.md "Fleet events")
    _h("fleet_start", "hydragnn_tpu/serve/fleet.py",
       "supervisor brought the replica pool up"),
    _h("replica_start", "hydragnn_tpu/serve/fleet.py",
       "one replica entered routing"),
    _h("replica_dead", "hydragnn_tpu/serve/fleet.py",
       "replica left routing involuntarily"),
    _h("replica_restart", "hydragnn_tpu/serve/fleet.py",
       "supervisor restarted a replica"),
    _h("replica_eject", "hydragnn_tpu/serve/fleet.py",
       "replica taken out of routing (breaker / restart storm)"),
    _h("replica_readmit", "hydragnn_tpu/serve/fleet.py",
       "ejected replica re-entered routing after cooldown"),
    _h("replica_drain", "hydragnn_tpu/serve/fleet.py",
       "drain-and-replace began"),
    _h("rolling_reload_start", "hydragnn_tpu/serve/fleet.py",
       "one-replica-at-a-time fleet reload began"),
    _h("rolling_reload_ok", "hydragnn_tpu/serve/fleet.py",
       "fleet reload completed on every replica"),
    _h("rolling_reload_rollback", "hydragnn_tpu/serve/fleet.py",
       "fleet reload aborted; swapped replicas rolled back"),
    _h("fleet_probe_error", "hydragnn_tpu/serve/fleet.py",
       "supervisor probe loop hit an unexpected error (loop survives)"),
    _h("fleet_retry", "hydragnn_tpu/serve/router.py",
       "router failed a request over to another replica"),
    _h("fleet_degraded", "hydragnn_tpu/serve/fleet.py",
       "live replicas dropped below quorum"),
    _h("fleet_empty", "hydragnn_tpu/serve/router.py",
       "a request found no live replica (503)"),
    # autoscaler + tenancy (docs/TELEMETRY.md "Autoscaler/tenancy kinds")
    _h("fleet_scale_up", "hydragnn_tpu/serve/fleet.py",
       "autoscaler added a replica (carries the drain-rate signal)"),
    _h("fleet_scale_down", "hydragnn_tpu/serve/fleet.py",
       "autoscaler retired a replica zero-drop after the quiet window"),
    _h("tenant_shed", "hydragnn_tpu/serve/router.py",
       "one tenant's request shed 429 (budget exceeded or chaos-hot)"),
    _h("tenant_evict", "hydragnn_tpu/serve/fleet.py",
       "LRU evicted a resident tenant engine from a replica"),
    _h("executable_evict", "hydragnn_tpu/serve/engine.py",
       "engine AOT-executable LRU evicted a compiled bucket"),
    # streaming data plane (docs/TELEMETRY.md "Streaming events")
    _h("stream_open", "hydragnn_tpu/train/trainer.py",
       "streaming data plane active (store, plan and window metadata)"),
    _h("stream_fallback", "hydragnn_tpu/train/trainer.py",
       "streaming requested but the run fell back to the in-memory path"),
    _h("stream_open_retry", "hydragnn_tpu/train/trainer.py",
       "one failed streaming store-open attempt that was retried with "
       "backoff before any fallback"),
    _h("stream_tail_grow", "hydragnn_tpu/train/trainer.py",
       "tail-mode store picked up newly sealed segments between epochs"),
    _h("stream_torn_segment", "hydragnn_tpu/data/stream/ingest.py",
       "ingest segment failed its manifest size check and was skipped"),
    # SLO monitoring (docs/TELEMETRY.md "Tracing")
    _h("slo_burn", "hydragnn_tpu/telemetry/slo.py",
       "burn-rate monitor: serving latency/shed budget burning faster "
       "than the configured multiple (edge-triggered per excursion)"),
]

HEALTH_KINDS: Dict[str, HealthKind] = {h.name: h for h in _HEALTH_LIST}


@dataclasses.dataclass(frozen=True)
class SpanName:
    name: str
    module: str  # recording module (repo-relative)
    desc: str


def _s(name, module, desc):
    return SpanName(name=name, module=module, desc=desc)


_SPAN_LIST = [
    # serving request path (docs/TELEMETRY.md "Tracing")
    _s("serve.request", "hydragnn_tpu/serve/server.py",
       "one HTTP request, admission to reply (router or single server)"),
    _s("serve.queue_wait", "hydragnn_tpu/serve/batcher.py",
       "enqueue -> flush pickup for one traced request"),
    _s("serve.flush", "hydragnn_tpu/serve/batcher.py",
       "one micro-batch flush; links the trace_ids it carried"),
    _s("serve.pad", "hydragnn_tpu/serve/engine.py",
       "bucket collation/padding inside a flush"),
    _s("serve.predict", "hydragnn_tpu/serve/engine.py",
       "device execution inside a flush (blocked-on-ready)"),
    # host regions of the trainer (utils/tracer start/stop: the timer
    # summary, the profiler's host plane, and spans while tracing is on)
    _s("train", "hydragnn_tpu/train/trainer.py",
       "one epoch's train dispatches (its opening is where an epoch "
       "begins)"),
    _s("validate", "hydragnn_tpu/train/trainer.py",
       "one epoch's validation dispatches"),
    _s("test", "hydragnn_tpu/train/trainer.py",
       "one epoch's test dispatches"),
    _s("metrics_fetch", "hydragnn_tpu/train/trainer.py",
       "the per-epoch sync: epoch.fetch + telemetry.flush"),
    _s("train.data_wait", "hydragnn_tpu/train/trainer.py",
       "blocking train-loader next() before a train dispatch"),
    _s("train.dispatch", "hydragnn_tpu/train/trainer.py",
       "host time of one train step call: argument ingest and enqueue, "
       "never the device's execution"),
    _s("eval.data_wait", "hydragnn_tpu/train/trainer.py",
       "blocking val/test-loader next() before an eval dispatch"),
    _s("eval.dispatch", "hydragnn_tpu/train/trainer.py",
       "host time of one eval step call"),
    _s("epoch.fetch", "hydragnn_tpu/train/trainer.py",
       "the device_get that drains the epoch's dispatch queue"),
    _s("telemetry.flush", "hydragnn_tpu/train/trainer.py",
       "flush_steps: fetch and write the buffered step records"),
    _s("epoch.tail", "hydragnn_tpu/train/trainer.py",
       "end of metrics_fetch to the next train: scheduler, history, "
       "checkpoint, JSONL, prints, set_epoch"),
    _s("checkpoint.save", "hydragnn_tpu/train/trainer.py",
       "one checkpoint write (best-model pickle, orbax, resume bundle)"),
    _s("data.collate", "hydragnn_tpu/data/dataloader.py",
       "collation of one padded batch (and its post_collate)"),
    _s("data.stack", "hydragnn_tpu/parallel/mesh.py",
       "DeviceStackLoader: np.stack of a dispatch group"),
    _s("data.h2d", "hydragnn_tpu/data/prefetch.py",
       "staging one batch on the device (resident staging, prefetcher)"),
    _s("setup.stats", "hydragnn_tpu/config/config.py",
       "DatasetStats.from_samples: one pass over every sample"),
    _s("setup.loaders", "hydragnn_tpu/data/dataloader.py",
       "create_dataloaders: pad specs and loaders"),
    _s("setup.init_state", "hydragnn_tpu/train/trainer.py",
       "create_train_state: model.init and optimizer init"),
    _s("setup.mfu_cost", "hydragnn_tpu/telemetry/logger.py",
       "the cost-analysis compile of the step behind mfu_est_pct"),
    _s("telemetry.step_programs", "hydragnn_tpu/telemetry/hlo_scopes.py",
       "StepPrograms.write in epoch 0's tail, while regions are annotated: "
       "each step program compiled again from its shapes (a cache read), "
       "its HLO text parsed into hlo_scopes.json"),
    _s("telemetry.program_memory", "hydragnn_tpu/telemetry/hlo_scopes.py",
       "inside telemetry.step_programs: one executable's "
       "memory_analysis() read into its program_memory record"),
]

SPAN_NAMES: Dict[str, SpanName] = {s.name: s for s in _SPAN_LIST}


@dataclasses.dataclass(frozen=True)
class ScopeName:
    name: str
    module: str  # module that opens the scope (repo-relative)
    desc: str


def _sc(name, module, desc):
    return ScopeName(name=name, module=module, desc=desc)


# jax.named_scope names inside the jitted steps: they reach the HLO
# op_name of every op traced under them, and so the device trace
_SCOPE_LIST = [
    _sc("step.loss", "hydragnn_tpu/train/trainer.py",
        "the value_and_grad call: jvp(...) below it is the forward, "
        "transpose(jvp(...)) the backward"),
    _sc("step.optimizer", "hydragnn_tpu/train/trainer.py",
        "optimizer update and apply_updates (ZeRO slice/gather included)"),
    _sc("step.metrics", "hydragnn_tpu/train/trainer.py",
        "telemetry norms and real node/edge counts"),
    _sc("step.guard", "hydragnn_tpu/train/trainer.py",
        "non-finite flag and the old/new state select"),
    _sc("step.eval", "hydragnn_tpu/train/trainer.py",
        "the forward and loss of an eval step"),
    _sc("comm.dp_psum", "hydragnn_tpu/parallel/mesh.py",
        "gradient/metric psum-pmean over the DP axes"),
    _sc("comm.zero_all_gather", "hydragnn_tpu/parallel/mesh.py",
        "ZeRO stage-2 param all_gather before the forward"),
    _sc("comm.halo_exchange", "hydragnn_tpu/parallel/mesh.py",
        "halo-row exchange assembling the extended graph shard"),
    # the language-model stack's own parts, below step.loss / step.eval
    _sc("lm.embed", "hydragnn_tpu/models/laguna.py",
        "embedding lookup of the node ids and each node's position "
        "inside its graph"),
    _sc("attn.proj", "hydragnn_tpu/models/laguna.py",
        "attention's norm, q/k/v/gate products, rotary, gate and output "
        "product"),
    _sc("attn.core", "hydragnn_tpu/ops/attention.py",
        "scores, softmax and values over each graph's nodes (the splash "
        "kernels and the block schedule made from node_gid, or the dense "
        "twin)"),
    _sc("ffn.dense", "hydragnn_tpu/models/sequence.py",
        "the dense gated feed-forward, node slice by node slice"),
    _sc("moe.route", "hydragnn_tpu/ops/moe.py",
        "router product, softmax, top-k, the held experts' loads"),
    _sc("moe.experts", "hydragnn_tpu/ops/moe.py",
        "the held slots' rows, grouped products and the nodes' sums (or "
        "the dense path)"),
    _sc("moe.gmm", "hydragnn_tpu/ops/moe.py",
        "the grouped matrix products alone (megablox gmm/tgmm, or "
        "ragged_dot), inside moe.experts"),
    _sc("moe.rows", "hydragnn_tpu/ops/moe.py",
        "the row movement alone, inside moe.experts: the held slots' "
        "index bookkeeping, nodes -> rows and rows -> nodes (the two "
        "row-walk kernels, or take / segment_sum)"),
    _sc("moe.shared", "hydragnn_tpu/models/sequence.py",
        "the shared expert's gated feed-forward"),
    _sc("moe.bias", "hydragnn_tpu/ops/moe.py",
        "a router under a correction bias: the real nodes' slots on each "
        "of ALL the experts, and the bias's step after a train step "
        "(models/sequence.py balance)"),
    _sc("lm.head", "hydragnn_tpu/models/laguna.py",
        "final norm and the untied head product"),
    _sc("lm.xent", "hydragnn_tpu/models/layers.py",
        "softmax cross-entropy against the next node's id"),
    # latent attention and multi-token prediction (models/glm_moe_lite.py)
    _sc("mla.down", "hydragnn_tpu/models/glm_moe_lite.py",
        "latent attention's input norm, the query and key/value "
        "bottlenecks (Wdq, Wdkv) and the two latent norms"),
    _sc("mla.up", "hydragnn_tpu/models/glm_moe_lite.py",
        "queries, keys and values rebuilt from the latents (Wuq, Wukv), "
        "rotary, the one rotary key joined to every head's key"),
    _sc("mla.core", "hydragnn_tpu/models/glm_moe_lite.py",
        "latent attention's scores, softmax and values: graph_attention, "
        "so attn.core lies inside it"),
    _sc("mla.out", "hydragnn_tpu/models/glm_moe_lite.py",
        "latent attention's output product (Wo)"),
    _sc("mtp.proj", "hydragnn_tpu/models/glm_moe_lite.py",
        "multi-token prediction: the next id's embedding, the two norms "
        "and eh_proj"),
    _sc("mtp.layer", "hydragnn_tpu/models/glm_moe_lite.py",
        "multi-token prediction's own expert layer (its mla.* and moe.* "
        "lie inside it)"),
    _sc("mtp.head", "hydragnn_tpu/models/glm_moe_lite.py",
        "multi-token prediction's final norm and the main head's product"),
    # state-space layers and the latent expert space (models/nemotron_h.py)
    _sc("ssm.in", "hydragnn_tpu/models/nemotron_h.py",
        "a Mamba-2 layer's norm and its one input product to [z | xBC | "
        "dt]"),
    _sc("ssm.conv", "hydragnn_tpu/models/nemotron_h.py",
        "the depthwise causal convolution that stops at graph boundaries, "
        "its silu, and dt's softplus"),
    _sc("ssm.scan", "hydragnn_tpu/ops/ssm.py",
        "the selective scan over each graph's nodes: the chunked form's "
        "four products, its decays and the scan over chunk states (or the "
        "sequential twin), and the D skip"),
    _sc("ssm.norm", "hydragnn_tpu/models/nemotron_h.py",
        "the gate silu(z) and the grouped RMS norm"),
    _sc("ssm.out", "hydragnn_tpu/models/nemotron_h.py",
        "a Mamba-2 layer's output product and residual"),
    _sc("moe.latent", "hydragnn_tpu/models/nemotron_h.py",
        "both projections of the latent expert space: hidden -> latent "
        "before dispatch, latent -> hidden after the combine"),
    # the double-gated short convolution (models/lfm2_moe.py)
    _sc("sconv.in", "hydragnn_tpu/models/lfm2_moe.py",
        "a short-convolution operator's norm and its one input product to "
        "[B | C | X]"),
    _sc("sconv.core", "hydragnn_tpu/models/lfm2_moe.py",
        "the gate B * X, the depthwise causal taps that stop at graph "
        "boundaries and the gate C * v (ops/sconv.py graph_short_conv)"),
    _sc("sconv.out", "hydragnn_tpu/models/lfm2_moe.py",
        "a short-convolution operator's output product"),
    # Gated DeltaNet (models/qwen3_next.py)
    _sc("gdn.in", "hydragnn_tpu/models/qwen3_next.py",
        "a Gated DeltaNet layer's zero-centred norm and its two input "
        "products to [q | k | v | z] and [b | a]"),
    _sc("gdn.conv", "hydragnn_tpu/models/qwen3_next.py",
        "the depthwise causal convolution of [q | k | v] that stops at "
        "graph boundaries, and its silu"),
    _sc("gdn.scan", "hydragnn_tpu/ops/gdn.py",
        "the gated delta rule over each graph's nodes: q's and k's l2 "
        "norms, g and beta, the chunked form's products, decays and unit "
        "lower-triangular inverse and the walk over chunk states (or the "
        "sequential twin)"),
    _sc("gdn.norm", "hydragnn_tpu/models/qwen3_next.py",
        "the RMS norm of each head's result under the gate silu(z)"),
    _sc("gdn.out", "hydragnn_tpu/models/qwen3_next.py",
        "a Gated DeltaNet layer's output product"),
]

SCOPE_NAMES: Dict[str, ScopeName] = {s.name: s for s in _SCOPE_LIST}


@dataclasses.dataclass(frozen=True)
class KernelName:
    name: str  # pl.pallas_call(name=...), <kernel>_<pass>
    module: str
    desc: str


def _kn(name, module, desc):
    return KernelName(name=name, module=module, desc=desc)


_EDGE_BLOCK_KERNELS = [
    ("egcl", "EGNN EGCL block (ops/egcl_mp.py)"),
    ("cgcnn", "CGCNN gated block (ops/cgcnn_mp.py)"),
    ("dn_tri_builder", "DimeNet triplet-table builder (ops/dn_tri.py)"),
]

# what a Mosaic custom call is called in a device trace
_KERNEL_LIST = [
    _kn(f"{k}_{p}", "hydragnn_tpu/ops/fused_block.py", f"{what}: {pdesc}")
    for k, what in _EDGE_BLOCK_KERNELS
    for p, pdesc in (("fwd", "forward"),
                     ("bwd_p", "backward, primary order: weights, "
                               "geometry, primary-side dx"),
                     ("bwd_s", "backward, other order: other-side dx"))
] + [
    _kn("gather_mul_seg_fwd", "hydragnn_tpu/ops/fused_mp.py",
        "gather x[send] (* w, an array or made in VMEM from its chain) "
        "-> sorted segment sum"),
    _kn("gather_mul_seg_bwd", "hydragnn_tpu/ops/fused_mp.py",
        "receiver-order backward pass: dw per edge (or, chain form, its "
        "pullback to the chain's weights) + windowed dx partials"),
    _kn("seg_sum_dense_fwd", "hydragnn_tpu/ops/fused_mp.py",
        "sorted segment sum on the dense schedule"),
    _kn("dn_tri_fwd", "hydragnn_tpu/ops/dn_tri.py",
        "DimeNet triplet message passing, forward"),
    _kn("dn_tri_bwd", "hydragnn_tpu/ops/dn_tri.py",
        "DimeNet triplet message passing, backward"),
    _kn("dn_post_mlp_fwd", "hydragnn_tpu/ops/row_mlp.py",
        "DimeNet post-triplet row MLP, forward"),
    _kn("dn_post_mlp_bwd", "hydragnn_tpu/ops/row_mlp.py",
        "DimeNet post-triplet row MLP, backward"),
    _kn("poly_scatter_fwd", "hydragnn_tpu/ops/poly_mp.py",
        "sorted segment moments (sum, sq, max/min, count)"),
    _kn("poly_gather_fwd", "hydragnn_tpu/ops/poly_mp.py",
        "gather x[send] -> segment moments (PNA)"),
    _kn("gat_attn_fwd", "hydragnn_tpu/ops/gat_mp.py",
        "GAT edge attention, forward"),
    _kn("gat_attn_bwd_r", "hydragnn_tpu/ops/gat_mp.py",
        "GAT edge attention backward, receiver order"),
    _kn("gat_attn_bwd_s", "hydragnn_tpu/ops/gat_mp.py",
        "GAT edge attention backward, sender order"),
    _kn("moe_nodes_to_rows", "hydragnn_tpu/ops/moe.py",
        "routed experts: the held slots' node rows into expert-sorted "
        "rows, zeros past the load"),
    _kn("moe_rows_to_nodes", "hydragnn_tpu/ops/moe.py",
        "routed experts: each node's weighted sum over its held slots' "
        "rows, node tile by node tile"),
]

KERNEL_NAMES: Dict[str, KernelName] = {k.name: k for k in _KERNEL_LIST}


TRACE_DOC_BEGIN = ("<!-- BEGIN GENERATED trace names "
                   "(graftlint --emit-docs) -->")
TRACE_DOC_END = "<!-- END GENERATED trace names -->"


def emit_trace_docs() -> str:
    """The region, scope and kernel name tables of docs/TELEMETRY.md
    "Tracing", between TRACE_DOC_BEGIN and TRACE_DOC_END."""
    def table(head, rows):
        return "\n".join([f"| {head} | module | what |", "|---|---|---|"]
                         + [f"| `{r.name}` | `{r.module}` | {r.desc} |"
                            for r in rows])

    return "\n\n".join([
        TRACE_DOC_BEGIN,
        "Host regions and spans (`SPAN_NAMES`): `utils/tracer` "
        "`start`/`stop` and the flight recorder's `span` / "
        "`record_interval`.",
        table("region or span", SPAN_NAMES.values()),
        "Device scopes (`SCOPE_NAMES`): `jax.named_scope` inside the "
        "jitted steps, read from an op's `op_name`.",
        table("scope", SCOPE_NAMES.values()),
        "Kernel names (`KERNEL_NAMES`): `pl.pallas_call(name=...)`, what "
        "a Mosaic custom call is called in a device trace.",
        table("kernel", KERNEL_NAMES.values()),
        TRACE_DOC_END,
    ]) + "\n"


KNOB_DOC_HEADER = """\
# Env knobs — the generated registry

GENERATED by `python tools/graftlint.py --emit-docs` from
`hydragnn_tpu/analysis/registry.py` — do not edit by hand; the lint gate
(`tests/test_lint.py`, rule REG002) fails when this file drifts from the
registry.  Config spellings follow the env-wins overlay convention
(`hydragnn_tpu/utils/env.py` truthiness rules: unset/empty/`0`/`false`
disables a flag).

| knob | config spelling | default | owning module | effect |
|---|---|---|---|---|
"""


def emit_knob_docs() -> str:
    """Render docs/KNOBS.md from the registry."""
    rows = []
    for name in sorted(KNOBS):
        k = KNOBS[name]
        cfg = f"`{k.config}`" if k.config else "—"
        default = k.default if k.default != "" else "—"
        rows.append(f"| `{k.name}` | {cfg} | {default} "
                    f"| `{k.module}` | {k.desc} |")
    return KNOB_DOC_HEADER + "\n".join(rows) + "\n"
