"""Flight recorder (docs/TELEMETRY.md "Tracing"): trace-identity
adoption, the bounded lock-guarded span ring, end-to-end serve spans
(request -> linked flush -> queue-wait/pad/predict children), trace ids
on shed/timeout answers and across failover, the trainer's host regions
as spans, the comm-vs-compute A/B probe, the SLO burn-rate monitor, and the
PR-15-style default-off purity claims."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from hydragnn_tpu.graph.batch import GraphSample, HeadSpec, PadSpec, collate
from hydragnn_tpu.graph.neighborlist import radius_graph
from hydragnn_tpu.models.base import GraphHeadCfg, ModelConfig
from hydragnn_tpu.models.create import create_model
from hydragnn_tpu.serve import (
    InferenceEngine,
    InferenceServer,
    InferenceState,
    ServingConfig,
)
from hydragnn_tpu.telemetry import MetricsLogger, TelemetryConfig
from hydragnn_tpu.telemetry.slo import BurnRateMonitor, SloConfig, tail_jsonl
from hydragnn_tpu.telemetry.trace import (
    SpanRecorder,
    chrome_trace,
    extract_trace_context,
    quantile,
)


def _sample(n=6, seed=0):
    rng = np.random.RandomState(seed)
    pos = rng.rand(n, 3).astype(np.float32) * 2.0
    return GraphSample(x=rng.rand(n, 1).astype(np.float32), pos=pos,
                       edge_index=radius_graph(pos, 1.2, 8))


_HEADS = [HeadSpec("energy", "graph", 1)]


@pytest.fixture(scope="module")
def _engine_mod():
    """ONE tiny SAGE engine for the whole module — each HTTP test
    reassigns `engine.telemetry` before building its server (the
    batcher inherits it at construction); the `engine` wrapper
    restores it after."""
    import jax

    cfg = ModelConfig(
        model_type="SAGE", input_dim=1, hidden_dim=8, output_dim=(1,),
        output_type=("graph",), graph_head=GraphHeadCfg(1, 8, 1, (8,)),
        node_head=None, task_weights=(1.0,), num_conv_layers=2)
    model = create_model(cfg)
    pads = [PadSpec.for_batch(2, 16, 64)]
    example = collate([_sample()], pads[0], _HEADS)
    variables = model.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        example, train=False)
    state = InferenceState(step=0, params=variables["params"],
                           batch_stats=variables.get("batch_stats", {}))
    eng = InferenceEngine(cfg, state, _HEADS, pads,
                          serving=ServingConfig(max_wait_ms=10),
                          telemetry=None)
    eng.warmup()
    return eng


@pytest.fixture
def engine(_engine_mod):
    prev = _engine_mod.telemetry
    yield _engine_mod
    _engine_mod.telemetry = prev


def _traced_logger(tmp_path=None, sinks=()):
    """Enabled MetricsLogger with the flight recorder armed; JSONL sink
    only when a directory is given (ring-only otherwise)."""
    return MetricsLogger(
        TelemetryConfig(enable=True, trace=True, trace_ring=512,
                        sinks=tuple(sinks)),
        run_name="trace_test",
        out_dir=str(tmp_path) if tmp_path is not None else None)


def _post(port, obj, headers=None, timeout=30.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/predict", data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read()), dict(r.headers)


def _sample_json(s, **extra):
    return {"x": s.x.tolist(), "pos": s.pos.tolist(),
            "edge_index": s.edge_index.tolist(), **extra}


# ---------------------------------------------------------------------------
# Trace identity: adopt-or-mint precedence, malformed values ignored
# ---------------------------------------------------------------------------


def test_extract_trace_context_precedence_and_malformed():
    tid, pid = "ab" * 16, "cd" * 8
    # traceparent wins (and carries the parent span id)
    ctx = extract_trace_context(
        {"traceparent": f"00-{tid}-{pid}-01", "X-Request-Id": "other"})
    assert (ctx.trace_id, ctx.parent_id, ctx.minted) == (tid, pid, False)
    # X-Request-Id next: arbitrary token schemes are adopted verbatim
    ctx = extract_trace_context({"X-Request-Id": "req_1:retry-2.a"})
    assert ctx.trace_id == "req_1:retry-2.a" and not ctx.minted
    # body-field spelling when no header is present
    ctx = extract_trace_context({}, {"trace_id": "bench-0-7"})
    assert ctx.trace_id == "bench-0-7" and not ctx.minted
    # malformed traceparent falls through to X-Request-Id, silently
    ctx = extract_trace_context(
        {"traceparent": "00-zznothex-01", "X-Request-Id": "fallback"})
    assert ctx.trace_id == "fallback" and not ctx.minted
    # header-splitting / oversize / non-string ids are treated as absent
    for bad in ("a b", "x\r\nSet-Cookie: no", "q" * 129, ""):
        ctx = extract_trace_context({"X-Request-Id": bad})
        assert ctx.minted and len(ctx.trace_id) == 32
    ctx = extract_trace_context({}, {"trace_id": 123})
    assert ctx.minted
    # minted ids are W3C-width and unique
    a, b = extract_trace_context({}), extract_trace_context({})
    assert a.trace_id != b.trace_id
    assert "-01" in a.traceparent() and a.trace_id in a.traceparent()


def test_quantile_nearest_rank():
    assert quantile([], 0.99) == 0.0
    vals = sorted(float(v) for v in range(1, 101))
    assert quantile(vals, 0.50) == 51.0
    assert quantile(vals, 0.99) == 100.0
    assert quantile([7.0], 0.99) == 7.0


# ---------------------------------------------------------------------------
# SpanRecorder: bounded ring, thread safety, percentiles, chrome export
# ---------------------------------------------------------------------------


def test_span_ring_bounded_overwrites_oldest():
    rec = SpanRecorder(ring=8)
    for i in range(50):
        rec.record_interval("serve.predict", 0.0, 0.001, seq=i)
    snap = rec.snapshot()
    assert len(snap) == 8  # bounded, whatever the request count
    assert [r["seq"] for r in snap] == list(range(42, 50))  # oldest-first
    pct = rec.percentiles()["serve.predict"]
    assert pct["count"] == 50  # lifetime count survives the overwrite
    assert pct["p50_ms"] == pytest.approx(1.0, rel=0.01)
    # the per-name reservoir is bounded too (no unbounded growth)
    assert len(rec._durations["serve.predict"]) <= 8
    assert rec.summary()["recorded"] == 50


def test_span_ring_lock_guarded_under_concurrent_writers():
    emitted = []
    rec = SpanRecorder(ring=64, emit=emitted.append)
    n_threads, per_thread = 8, 200

    def writer(wid):
        for i in range(per_thread):
            with rec.span("serve.request", trace_id=f"t{wid}-{i}"):
                pass
            rec.record_interval("serve.queue_wait", 0.0, 0.0005)

    threads = [threading.Thread(target=writer, args=(w,))
               for w in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * per_thread
    pct = rec.percentiles()
    assert pct["serve.request"]["count"] == total
    assert pct["serve.queue_wait"]["count"] == total
    assert rec.summary()["recorded"] == 2 * total
    assert len(rec.snapshot()) == 64
    assert len(emitted) == 2 * total  # every span reached the JSONL hook


def test_span_context_manager_and_chrome_export():
    rec = SpanRecorder(ring=16)
    with rec.span("serve.flush", trace_id="tr1", bucket=4):
        time.sleep(0.002)
    rec.record_interval("train.dispatch", 1.0, 1.5, trace_id="run",
                        parent_id="abcd")
    doc = chrome_trace(rec.snapshot() + [{"event": "step"}])  # non-spans skipped
    evs = doc["traceEvents"]
    assert len(evs) == 2
    flush = next(e for e in evs if e["name"] == "serve.flush")
    assert flush["ph"] == "X" and flush["pid"] == "serve"
    assert flush["dur"] >= 2000  # microseconds
    assert flush["args"]["bucket"] == 4 and flush["args"]["trace_id"] == "tr1"
    step = next(e for e in evs if e["name"] == "train.dispatch")
    assert step["pid"] == "train" and step["dur"] == pytest.approx(5e5)
    assert step["args"]["parent_id"] == "abcd"


# ---------------------------------------------------------------------------
# End-to-end serve: request span + linked flush + phase children in JSONL
# ---------------------------------------------------------------------------


def test_server_traces_end_to_end(tmp_path, engine):
    tel = _traced_logger(tmp_path, sinks=("jsonl",))
    engine.telemetry = tel  # before the server: the batcher inherits it
    srv = InferenceServer(engine,
                          serving=ServingConfig(port=0, max_wait_ms=5))
    srv.start()
    rids = [f"e2e-{i}" for i in range(4)]
    try:
        for rid in rids:
            code, out, hdrs = _post(
                srv.port, _sample_json(_sample(5, seed=int(rid[-1]))),
                headers={"X-Request-Id": rid})
            assert code == 200
            assert out["trace_id"] == rid  # body echo
            assert hdrs.get("X-Request-Id") == rid  # header echo
        with urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/metrics", timeout=10) as r:
            m = json.loads(r.read())
        # /metrics span-latency breakdown: queue-wait vs predict
        assert m["spans"]["serve.request"]["count"] >= 4
        assert m["spans"]["serve.queue_wait"]["count"] >= 4
        assert m["spans"]["serve.predict"]["p99_ms"] >= 0.0
    finally:
        srv.shutdown()
        tel.finalize()
    recs = [json.loads(line)
            for line in open(tel.jsonl_path) if line.strip()]
    spans = [r for r in recs if r.get("event") == "span"]
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"], []).append(s)
    # one request span per stamped id, status attached
    req_ids = {s["trace_id"] for s in by_name["serve.request"]}
    assert set(rids) <= req_ids
    assert all(s["status"] == 200 and s["dur_ms"] >= 0.0
               for s in by_name["serve.request"])
    # every traced request is linked from some flush span, and the flush
    # has pad/predict children parented to its span_id on its trace
    linked = {t for s in by_name["serve.flush"] for t in s.get("links", [])}
    assert set(rids) <= linked
    for flush in by_name["serve.flush"]:
        kids = [s for s in spans
                if s.get("parent_id") == flush["span_id"]]
        assert {k["name"] for k in kids} >= {"serve.pad", "serve.predict"}
    # queue-wait rides the REQUEST's trace (client id resolves the story)
    qw_ids = {s["trace_id"] for s in by_name["serve.queue_wait"]}
    assert set(rids) <= qw_ids
    # the manifest carries the span summary block
    manifest = next(r for r in recs if r.get("event") == "manifest")
    assert manifest["spans"]["recorded"] >= len(spans)
    assert "serve.request" in manifest["spans"]["by_name"]


def test_shed_and_timeout_answers_carry_trace_id(engine):
    tel = _traced_logger()
    engine.telemetry = tel
    srv = InferenceServer(engine,
                          serving=ServingConfig(port=0, max_wait_ms=5))
    srv.start()
    try:
        # warm the drain-rate estimate so admission control can shed
        code, _, _ = _post(srv.port, _sample_json(_sample(5, seed=1)),
                           headers={"X-Request-Id": "warm-1"})
        assert code == 200
        # an impossible deadline -> 429, and the answer must quote the id
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.port,
                  _sample_json(_sample(5, seed=2), timeout_ms=0.001),
                  headers={"X-Request-Id": "shed-me"})
        assert ei.value.code == 429
        body = json.loads(ei.value.read())
        assert body["trace_id"] == "shed-me"
        assert ei.value.headers.get("X-Request-Id") == "shed-me"
        # malformed body: the id was adopted from the HEADERS before the
        # body read, so even a 400 quotes it
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/predict", data=b"not json",
            headers={"Content-Type": "application/json",
                     "X-Request-Id": "bad-body"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        assert ei.value.code == 400
        assert json.loads(ei.value.read())["trace_id"] == "bad-body"
        # error request spans land in the ring with their status
        statuses = {}
        for s in tel.spans.snapshot():
            if s["name"] == "serve.request":
                statuses[s["trace_id"]] = s["status"]
        assert statuses.get("shed-me") == 429
        assert statuses.get("bad-body") == 400
    finally:
        srv.shutdown()


def test_predict_timeout_504_carries_trace_id(engine):
    from hydragnn_tpu.resilience import ServeChaos

    engine.telemetry = _traced_logger()
    srv = InferenceServer(
        engine,
        serving=ServingConfig(port=0, max_wait_ms=0, predict_timeout_s=0.05,
                              breaker_threshold=0),  # breaker off: raw 504
        chaos=ServeChaos(predict_ms=400.0, lat_from=1))
    srv.start()
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(srv.port, _sample_json(_sample(5, seed=3)),
                  headers={"X-Request-Id": "slow-one"})
        assert ei.value.code == 504
        assert json.loads(ei.value.read())["trace_id"] == "slow-one"
        assert ei.value.headers.get("X-Request-Id") == "slow-one"
    finally:
        srv.shutdown()


def test_trace_id_survives_midflight_failover(engine):
    """The PR-8 chaos path: replica 0 dies UNDER the request; the router
    retries on replica 1 and the answer still quotes the client's id —
    and the fleet-edge request span records the whole story as ONE
    trace."""
    from hydragnn_tpu.serve import (
        FleetRouter,
        FleetSupervisor,
        InProcessReplica,
    )
    from hydragnn_tpu.serve.fleet import ReplicaDeadError

    eng = engine
    serving = ServingConfig(port=0, max_wait_ms=2,
                            request_deadline_ms=10_000.0,
                            fleet_probe_s=0.03,
                            fleet_restart_backoff_s=0.05)
    tel = _traced_logger()
    replicas = [InProcessReplica(i, eng.fork, serving,
                                 MetricsLogger.disabled())
                for i in range(2)]
    fleet = FleetSupervisor(replicas, serving, telemetry=tel)
    router = FleetRouter(fleet, serving=serving, cfg=eng.cfg, telemetry=tel)
    router.start()
    try:
        def dead_predict(req, deadline_s):
            raise ReplicaDeadError("simulated mid-request death")

        fleet.replicas[0].predict = dead_predict
        for i in range(4):  # whatever po2 picks first, all must fail over
            rid = f"failover-{i}"
            code, out, hdrs = _post(
                router.port, _sample_json(_sample(5, seed=i),
                                          timeout_ms=10_000),
                headers={"X-Request-Id": rid})
            assert code == 200
            assert out["replica"] == 1
            assert out["trace_id"] == rid
            assert hdrs.get("X-Request-Id") == rid
        assert router.metrics()["router"]["failovers"] >= 1
        spans = {s["trace_id"]: s for s in tel.spans.snapshot()
                 if s["name"] == "serve.request"}
        for i in range(4):
            assert spans[f"failover-{i}"]["status"] == 200
    finally:
        router.shutdown()


# ---------------------------------------------------------------------------
# Train-step phase attribution
# ---------------------------------------------------------------------------


def test_regions_reach_the_span_recorder_without_a_sync(monkeypatch):
    """With tracing on the trainer's host regions become spans through
    the RegionSpans adapter, and the epoch loop stays the same program:
    the same dispatches, no ``block_until_ready`` per dispatch."""
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.telemetry.trace import RegionSpans
    from hydragnn_tpu.train.trainer import _run_epoch
    from hydragnn_tpu.utils import tracer as tr

    syncs, dispatched = [], []
    real_block = jax.block_until_ready
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda x: (syncs.append(1), real_block(x))[1])

    def step_fn(state, g):
        dispatched.append(g)
        return state + g, {"loss": jnp.float32(g),
                           "num_graphs": jnp.float32(1.0),
                           "task_0": jnp.float32(g)}

    def epoch():
        dispatched.clear()
        state, acc = _run_epoch(step_fn, 0, [1, 2, 3, 4], True)
        return state, float(acc[0]), list(dispatched)

    untraced = epoch()
    rec = SpanRecorder(ring=64)
    tr.register("spans", RegionSpans(rec))
    try:
        traced = epoch()
    finally:
        tr.unregister("spans")
    assert traced == untraced == (10, 10.0, [1, 2, 3, 4])
    assert not syncs
    pct = rec.percentiles()
    assert pct["train.dispatch"]["count"] == 4
    # four batches and the next() that finds the loader exhausted
    assert pct["train.data_wait"]["count"] == 5
    assert set(pct) == {"train.dispatch", "train.data_wait"}
    assert len({s["trace_id"] for s in rec.snapshot()}) == 1


class _NestingTracer:
    """Writes every region down and holds it to the contract: a stop
    closes the innermost open region of its thread."""

    def __init__(self):
        self.open = {}              # thread -> stack of names
        self.closed = []            # (name, thread, depth)
        self.faults = []

    def start(self, name):
        self.open.setdefault(threading.get_ident(), []).append(name)

    def stop(self, name):
        stack = self.open.get(threading.get_ident(), [])
        if not stack or stack[-1] != name:
            self.faults.append((name, list(stack)))
            return
        stack.pop()
        self.closed.append((name, threading.get_ident(), len(stack)))

    def reset(self):
        pass


def test_training_regions_close_nest_and_are_declared(tmp_path, monkeypatch):
    """Two epochs through the real ``train_validate_test`` (DP mesh path,
    resident staging, a checkpoint, tracing on): every region the trainer
    and the data path open closes, nests, and is declared; the flight
    recorder's manifest block has them."""
    from test_resilience import _Loaders, _run

    from hydragnn_tpu.analysis.registry import SPAN_NAMES
    from hydragnn_tpu.utils import tracer as tr

    monkeypatch.setenv("HYDRAGNN_RESIDENT_DATASET", "1")
    monkeypatch.setenv("HYDRAGNN_STEPS_PER_DISPATCH", "1")
    rec = _NestingTracer()
    tr.register("nesting", rec)
    tel = _traced_logger(tmp_path / "tel", sinks=("jsonl",))
    try:
        _, hist = _run(_Loaders(n_train=128), tmp_path, "regions",
                       num_epoch=2, use_mesh_dp=True, telemetry=tel,
                       training_extra={"Checkpoint": True})
    finally:
        tr.unregister("nesting")
    assert len(hist["train"]) == 2
    assert not rec.faults, rec.faults
    assert not any(rec.open.values()), rec.open
    assert not tr.has("spans")          # the adapter left with the run
    names = {n for n, _t, _d in rec.closed}
    assert names <= set(SPAN_NAMES), names - set(SPAN_NAMES)
    assert names >= {
        "train", "validate", "test", "metrics_fetch", "train.data_wait",
        "train.dispatch", "eval.data_wait", "eval.dispatch", "epoch.fetch",
        "telemetry.flush", "epoch.tail", "checkpoint.save", "data.collate",
        "data.stack", "data.h2d", "setup.mfu_cost"}, names
    depth = {n: d for n, _t, d in rec.closed}
    assert depth["train"] == depth["metrics_fetch"] == depth[
        "epoch.tail"] == 0
    assert depth["train.dispatch"] == depth["epoch.fetch"] == 1
    assert depth["checkpoint.save"] == 1        # inside epoch.tail
    # epoch 0 stages the resident corpus; epoch 1 collates nothing
    n_tail = sum(1 for n, _t, _d in rec.closed if n == "epoch.tail")
    assert n_tail == 2
    recs = [json.loads(line) for line in open(tel.jsonl_path)]
    manifest = next(r for r in recs if r["event"] == "manifest")
    by_name = manifest["spans"]["by_name"]
    for name in ("train.data_wait", "train.dispatch", "epoch.fetch",
                 "epoch.tail", "data.collate", "data.h2d"):
        assert by_name[name]["count"] >= 1, name
    assert "train.step" not in by_name and "train.h2d" not in by_name


_HLO = """HloModule jit_step, is_scheduled=true

FileNames
1 "/x/hydragnn_tpu/models/schnet.py"
2 "/venv/flax/linen/linear.py"

FunctionNames
1 "SCFConv.__call__"

FileLocations
1 {file_name_id=1 function_name_id=1 line=111 end_line=111 column=1 end_column=2}
2 {file_name_id=2 function_name_id=1 line=287 end_line=287 column=1 end_column=2}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %mul.1 = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(step)/step.loss/jvp(M)/lin/mul" stack_frame_id=2}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %copy-start.1 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%fusion.1)
  %copy-done.1 = f32[8]{0} copy-done(%copy-start.1)
  ROOT %add.1 = f32[8]{0} add(%copy-done.1, %a), metadata={op_name="jit(step)/step.optimizer/add" stack_frame_id=1}
}
"""


def test_instruction_scopes_from_hlo_text():
    from hydragnn_tpu.telemetry.hlo_scopes import instruction_scopes

    got = instruction_scopes(_HLO)
    fwd = "jit(step)/step.loss/jvp(M)/lin/mul"
    # a fusion without op_name takes its root's; the line is the
    # package's own frame up the chain (frame 1 is its own parent: no
    # loop); an instruction inside the fusion is no op of its own
    assert got["fusion.1"] == ["f32[8]", fwd, 0, "models/schnet.py:111"]
    assert "mul.1" not in got and "p" not in got
    # the compiler's copies have no name: the nearest operand's, marked
    assert got["copy-start.1"] == ["f32[8]", fwd, 1, "models/schnet.py:111"]
    assert got["copy-done.1"] == ["f32[8]", fwd, 1, "models/schnet.py:111"]
    assert got["add.1"] == ["f32[8]", "jit(step)/step.optimizer/add", 0,
                            "models/schnet.py:111"]
    assert got["a"] == ["f32[8]", "", 0, ""]


def test_step_programs_writes_scopes_once(tmp_path):
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.telemetry.hlo_scopes import StepPrograms
    from hydragnn_tpu.train.trainer import phase

    @jax.jit
    def step(x):
        with phase("step.loss"):
            return jnp.sin(x) * 2.0

    notes = StepPrograms()
    watched = notes.watch(step)
    a, b = jnp.ones(4), jnp.ones(9)
    for x in (a, a, b):             # two shapes: two executables
        assert jnp.allclose(watched(x), step(x))
    path = tmp_path / "telemetry" / "hlo_scopes.json"
    notes.write(str(path))
    programs = json.loads(path.read_text())["programs"]
    assert [p["name"] for p in programs] == ["jit_step", "jit_step"]
    for p in programs:
        assert any("step.loss" in scope
                   for _shape, scope, _inh, _src in p["instructions"].values())
    path.unlink()
    watched(jnp.ones(5))            # after the write: no more notes
    notes.write(str(path))
    assert not path.exists()


# ---------------------------------------------------------------------------
# Comm-vs-compute A/B probe (forced 8-device CPU mesh via conftest)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mesh_harness():
    import jax

    from test_resilience import _batch, _model

    from hydragnn_tpu.parallel.mesh import (
        make_mesh,
        replicate_state,
        stack_batches,
    )
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.trainer import create_train_state

    cfg, model = _model()
    opt = select_optimizer({"type": "AdamW", "learning_rate": 1e-3})
    mesh = make_mesh()
    n_dev = len(jax.devices())
    batches = stack_batches([_batch(seed=i) for i in range(n_dev)])
    state = replicate_state(create_train_state(model, _batch(), opt), mesh)
    return cfg, model, opt, mesh, state, batches


def test_scopes_and_kernel_names_change_metadata_only(mesh_harness,
                                                      monkeypatch):
    """Phase scopes, ``comm.*`` regions and kernel names are names: the
    lowered StableHLO (printed without debug locations) is the one a build
    with the helpers stubbed out lowers, so the traced program IS the
    production program.  The names reach the compiled program's op
    metadata, where a device trace reads them."""
    import contextlib

    from jax.experimental import pallas as pl

    from hydragnn_tpu.parallel import mesh as mesh_mod
    from hydragnn_tpu.parallel.mesh import make_dp_train_step
    from hydragnn_tpu.train import trainer as trainer_mod

    import jax

    from test_fused_block import _model_batch, _model_cfg

    from hydragnn_tpu.train.trainer import create_train_state

    cfg, model, opt, mesh, state, batches = mesh_harness
    # a one-device step on the fused backend: it has the kernels
    monkeypatch.setenv("HYDRAGNN_AGGR_BACKEND", "fused")
    f_cfg = _model_cfg("SchNet")
    f_model = create_model(f_cfg)
    f_batch = _model_batch("SchNet", 5)
    f_state = jax.eval_shape(
        lambda b: create_train_state(f_model, b, opt), f_batch)

    def lowered():
        return make_dp_train_step(
            model, cfg, opt, mesh, telemetry_metrics=True,
            nonfinite_guard=True).lower(state, batches)

    def lowered_fused():
        return jax.jit(trainer_mod.make_train_step(
            f_model, f_cfg, opt)).lower(f_state, f_batch).as_text()

    named, named_fused = lowered(), lowered_fused()
    no_scope = lambda name: contextlib.nullcontext()  # noqa: E731
    monkeypatch.setattr(trainer_mod, "phase", no_scope)
    monkeypatch.setattr(mesh_mod, "phase", no_scope)
    monkeypatch.setattr(mesh_mod, "comm_region", no_scope)
    real_call = pl.pallas_call
    monkeypatch.setattr(
        pl, "pallas_call",
        lambda *a, name=None, **kw: real_call(*a, **kw))
    bare = lowered()
    assert named.as_text() == bare.as_text()
    assert named_fused == lowered_fused()
    assert "step.loss" not in named.as_text()
    named_hlo, bare_hlo = named.compile().as_text(), bare.compile().as_text()
    for scope in ("step.loss", "step.optimizer", "step.metrics",
                  "step.guard", "comm.dp_psum"):
        assert scope in named_hlo, scope
        assert scope not in bare_hlo, scope


def test_dp_comms_probe_reports_split_and_preserves_state(mesh_harness):
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.telemetry.comms import comm_split, dp_comms_probe

    cfg, model, opt, mesh, state, batches = mesh_harness
    out = dp_comms_probe(model, cfg, opt, mesh, state, batches, iters=1)
    assert out["path"] == "dp"
    assert out["n_devices"] == len(jax.devices())
    assert out["comm_ms"] >= 0.0 and out["compute_ms"] >= 0.0
    assert out["step_ms"] == pytest.approx(
        out["comm_ms"] + out["compute_ms"], abs=0.01)
    assert 0.0 <= out["comm_pct"] <= 100.0
    assert "comm.dp_psum_ms" in out["parts"]
    assert "upper bound" in out["method"]
    # the probe timed COPIES: the caller's state was never donated
    leaf = jax.tree.leaves(state.params)[0]
    assert bool(jnp.isfinite(jnp.sum(leaf)))

    # split arithmetic clamps: comm can never exceed the step
    s = comm_split(2.0, 5.0)
    assert s == {"step_ms": 2.0, "comm_ms": 2.0, "compute_ms": 0.0,
                 "comm_pct": 100.0}


def test_log_comms_lands_in_manifest(tmp_path):
    tel = _traced_logger(tmp_path, sinks=("jsonl",))
    tel.log_comms({"path": "dp", "step_ms": 4.0, "comm_ms": 1.0,
                   "compute_ms": 3.0, "comm_pct": 25.0})
    tel.finalize()
    recs = [json.loads(line)
            for line in open(tel.jsonl_path) if line.strip()]
    assert any(r.get("event") == "comms" and r["path"] == "dp"
               for r in recs)
    manifest = next(r for r in recs if r.get("event") == "manifest")
    assert manifest["comms"]["comm_pct"] == 25.0


# ---------------------------------------------------------------------------
# SLO burn-rate monitor
# ---------------------------------------------------------------------------


class _Tel:
    def __init__(self):
        self.events = []

    def health(self, kind, **fields):
        self.events.append((kind, fields))


def test_slo_monitor_fires_on_synthetic_burn_edge_triggered():
    tel = _Tel()
    mon = BurnRateMonitor(
        SloConfig(shed_budget=0.05, window_s=60.0, burn=2.0),
        telemetry=tel)
    # 10 accepted answers, then a shed storm: 5/15 = 33% >> 2x5% = 10%
    for i in range(10):
        mon.observe({"event": "step", "source": "serve", "num_graphs": 1,
                     "predict_ms": 5.0, "wait_ms": 1.0}, now=float(i))
    assert mon.check(now=10.0) is None  # compliant so far
    for i in range(5):
        mon.observe({"event": "health", "kind": "request_shed"},
                    now=10.0 + i)
    v = mon.check(now=15.0)
    assert v is not None and v["budget"] == "shed_ratio"
    assert v["shed"] == 5 and v["accepted"] == 10
    assert [k for k, _ in tel.events] == ["slo_burn"]
    # edge-triggered: the SAME excursion stays quiet
    assert mon.check(now=16.0) is None
    assert mon.fired == 1
    # a compliant window re-arms (sheds age out), a fresh burn re-fires
    assert mon.check(now=200.0) is None
    for i in range(5):
        mon.observe({"event": "health", "kind": "queue_full"},
                    now=300.0 + i)
    mon.observe({"event": "step", "source": "serve", "num_graphs": 1,
                 "predict_ms": 5.0, "wait_ms": 1.0}, now=305.0)
    assert mon.check(now=306.0) is not None
    assert mon.fired == 2


def test_slo_monitor_latency_budget_uses_request_spans():
    tel = _Tel()
    mon = BurnRateMonitor(
        SloConfig(p99_ms=100.0, shed_budget=1.0, window_s=60.0),
        telemetry=tel)
    for i in range(20):
        mon.observe({"event": "span", "name": "serve.request",
                     "dur_ms": 250.0}, now=float(i))
    v = mon.check(now=21.0)
    assert v is not None and v["budget"] == "latency_p99"
    assert v["p99_ms"] == 250.0 and v["target_ms"] == 100.0
    assert tel.events[0][0] == "slo_burn"


def test_slo_monitor_quiet_on_compliant_stream():
    tel = _Tel()
    mon = BurnRateMonitor(
        SloConfig(p99_ms=1000.0, shed_budget=0.05, window_s=60.0),
        telemetry=tel)
    for i in range(100):
        mon.observe({"event": "step", "source": "serve", "num_graphs": 4,
                     "predict_ms": 3.0, "wait_ms": 2.0}, now=float(i))
        assert mon.check(now=float(i)) is None
    # one shed among 400 accepted: well under budget
    mon.observe({"event": "health", "kind": "request_shed"}, now=100.0)
    assert mon.check(now=101.0) is None
    assert mon.fired == 0 and tel.events == []


def test_slo_tail_jsonl_offline_replay(tmp_path):
    burn = tmp_path / "burn.jsonl"
    with open(burn, "w") as f:
        for i in range(10):
            f.write(json.dumps({"event": "step", "source": "serve",
                                "num_graphs": 1, "predict_ms": 1.0,
                                "wait_ms": 0.0, "t": float(i)}) + "\n")
        f.write("not json — skipped, not fatal\n")
        for i in range(10):
            f.write(json.dumps({"event": "health", "kind": "queue_full",
                                "t": 10.0 + i}) + "\n")
    cfg = SloConfig(shed_budget=0.05, window_s=60.0, burn=2.0)
    mon, violations = tail_jsonl(str(burn), cfg)
    assert len(violations) == 1  # edge-triggered: one per excursion
    assert violations[0]["budget"] == "shed_ratio"
    assert mon.fired == 1

    quiet = tmp_path / "quiet.jsonl"
    with open(quiet, "w") as f:
        for i in range(50):
            f.write(json.dumps({"event": "step", "source": "serve",
                                "num_graphs": 2, "predict_ms": 1.0,
                                "wait_ms": 0.0, "t": float(i)}) + "\n")
    mon, violations = tail_jsonl(str(quiet), cfg)
    assert violations == [] and mon.fired == 0


def test_slo_config_env_overrides(monkeypatch):
    monkeypatch.setenv("HYDRAGNN_SLO_P99_MS", "250")
    monkeypatch.setenv("HYDRAGNN_SLO_SHED_BUDGET", "0.02")
    monkeypatch.setenv("HYDRAGNN_SLO_WINDOW_S", "30")
    monkeypatch.setenv("HYDRAGNN_SLO_BURN", "4.0")
    cfg = SloConfig(p99_ms=1.0, shed_budget=0.5, window_s=5.0, burn=1.0)
    assert (cfg.p99_ms, cfg.shed_budget, cfg.window_s, cfg.burn) \
        == (250.0, 0.02, 30.0, 4.0)
    monkeypatch.setenv("HYDRAGNN_SLO_BURN", "not-a-float")
    assert SloConfig(burn=3.0).burn == 3.0  # malformed env falls back


def test_instruction_scopes_reads_past_a_multi_line_custom_call():
    """A kernel with a multi-line attribute prints its custom call over
    several lines, the last beginning with ``}}``: it is one instruction,
    not the end of the computation (the splash attention kernels; before
    the fix every later instruction of the computation went unnamed)."""
    from hydragnn_tpu.telemetry.hlo_scopes import instruction_scopes

    text = """HloModule jit_step

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %splash_fwd.1 = f32[8]{0} custom-call(%a), custom_call_target="tpu_custom_call", backend_config={"custom_call_config": {"body": "x",
"xprof_metadata":"{\\"block_q\\": 512}"
}}, metadata={op_name="jit(step)/step.loss/jvp(M)/attn.core/pallas_call"}
  ROOT %add.1 = f32[8]{0} add(%splash_fwd.1, %a), metadata={op_name="jit(step)/step.optimizer/add"}
}
"""
    got = instruction_scopes(text)
    assert got["splash_fwd.1"][:3] == [
        "f32[8]", "jit(step)/step.loss/jvp(M)/attn.core/pallas_call", 0]
    assert got["add.1"][:3] == ["f32[8]", "jit(step)/step.optimizer/add", 0]


# ---------------------------------------------------------------------------
# The build record (telemetry/programs.py): one ``program`` event per
# program JAX builds, with the region, epoch and step that caused it
# ---------------------------------------------------------------------------


def _fresh_jit(name):
    """A jitted function nothing has built yet (a new function object is a
    new program to ``jit``, whatever an earlier test compiled)."""
    import jax
    import jax.numpy as jnp

    def fn(x):
        return jnp.sin(x) * 3.0 + 1.0

    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def _named(records, name):
    return [r for r in records if r.get("event") == "program"
            and r["name"] == name]


def test_current_region_is_per_thread_and_outlives_the_tracers():
    from hydragnn_tpu.utils import tracer as tr

    class Refuses:
        def start(self, name):
            if name == "train":
                raise RuntimeError("window closed")

        def stop(self, name):
            pass

    def on_a_thread_of_its_own():
        # (whatever an earlier test of this process left open on the main
        # thread is the main thread's)
        assert tr.current() is None and tr.open_regions() == ()
        tr.start("epoch.tail")
        tr.start("checkpoint.save")
        assert tr.current() == "checkpoint.save"
        tr.initialize()                 # the tracers go, the order stays
        seen = []
        other = threading.Thread(target=lambda: seen.append(tr.current()))
        other.start()
        other.join(timeout=10)
        assert seen == [None]           # another thread's regions are its own
        tr.stop("epoch.tail")           # closed out of order: that one goes
        assert tr.open_regions() == ("checkpoint.save",)
        tr.stop("checkpoint.save")
        tr.stop("checkpoint.save")      # a stop too many is no fault
        assert tr.current() is None
        # a tracer that refuses a region by raising leaves none open
        tr.register("refuses", Refuses())
        try:
            with pytest.raises(RuntimeError):
                tr.start("train")
        finally:
            tr.unregister("refuses")
        assert tr.current() is None
        # what an exception left open is closed down to where a loop began
        tr.start("setup.loaders")
        tr.start("train")
        tr.start("train.dispatch")
        tr.close_to(1)
        assert tr.open_regions() == ("setup.loaders",)
        tr.close_to(0)
        faults.clear()

    faults = ["did not finish"]
    worker = threading.Thread(target=on_a_thread_of_its_own)
    worker.start()
    worker.join(timeout=60)
    assert not faults


def test_a_build_inside_a_region_leaves_one_record_naming_it():
    import jax.numpy as jnp

    from hydragnn_tpu.telemetry import programs
    from hydragnn_tpu.utils import tracer as tr

    step = _fresh_jit("built_in_dispatch")
    x = jnp.ones(16)                    # its eager builds are not the step's
    before = len(_named(programs.RECORDER.backlog(), "built_in_dispatch"))
    with tr.timer("train.dispatch"):
        step(x)
        step(x)                         # the second call builds nothing
    mine = _named(programs.RECORDER.backlog(), "built_in_dispatch")
    assert len(mine) - before == 1
    rec = mine[-1]
    assert rec["region"] == "train.dispatch"
    assert rec["epoch"] is None and rec["step"] is None
    assert rec["trace_s"] > 0 and rec["lower_s"] > 0 and rec["build_s"] > 0
    assert rec["t_start"] <= rec["t"] - rec["build_s"] + 1e-3
    assert abs(rec["t"] - time.time()) < 60     # unix seconds
    # tests/conftest.py turns the persistent cache off
    assert rec["cache"] == "off" and rec["cache_load_s"] == 0.0


def test_a_build_on_a_second_thread_carries_that_threads_region():
    import jax.numpy as jnp

    from hydragnn_tpu.telemetry import programs
    from hydragnn_tpu.utils import tracer as tr

    staged, x = _fresh_jit("built_by_prefetch"), jnp.ones(8)

    def prefetch():
        with tr.timer("data.h2d"):
            staged(x)

    with tr.timer("train.dispatch"):
        worker = threading.Thread(target=prefetch)
        worker.start()
        worker.join(timeout=60)
        assert not worker.is_alive()
    rec = _named(programs.RECORDER.backlog(), "built_by_prefetch")[-1]
    assert rec["region"] == "data.h2d"


def test_backlog_goes_to_the_first_logger_with_sinks_in_order(tmp_path):
    import jax.numpy as jnp

    from hydragnn_tpu.telemetry import programs

    # a worker that has built thousands of programs comes here with a full
    # backlog, whose oldest records go as this test adds its own: a logger
    # with sinks takes what waits, and hands the recorder back
    MetricsLogger(TelemetryConfig(enable=True, sinks=("jsonl",)),
                  run_name="drain", out_dir=str(tmp_path / "drain")).finalize()
    x = jnp.ones(4)
    _fresh_jit("before_logger_a")(x)
    _fresh_jit("before_logger_b")(x)
    waiting = [r["seq"] for r in programs.RECORDER.backlog()]
    assert waiting == sorted(waiting) and len(waiting) >= 2
    # telemetry off: nothing is taken, nothing is written
    off = MetricsLogger(TelemetryConfig(enable=False), run_name="off",
                        out_dir=str(tmp_path / "off"))
    _fresh_jit("while_off")(x)
    off.finalize()
    assert not (tmp_path / "off").exists()
    assert [r["seq"] for r in programs.RECORDER.backlog()][:len(waiting)] \
        == waiting
    tel = MetricsLogger(TelemetryConfig(enable=True, sinks=("jsonl",)),
                        run_name="adopts", out_dir=str(tmp_path / "tel"))
    assert programs.RECORDER.backlog() == []
    tel.begin_epoch(3)
    _fresh_jit("after_logger")(x)
    tel.finalize()
    _fresh_jit("after_finalize")(x)
    recs = [json.loads(line) for line in open(tel.jsonl_path)]
    built = [r for r in recs if r["event"] == "program"]
    seqs = [r["seq"] for r in built]
    assert seqs == sorted(seqs) and set(waiting) <= set(seqs)
    names = [r["name"] for r in built]
    assert names.index("before_logger_a") < names.index("before_logger_b") \
        < names.index("while_off") < names.index("after_logger")
    assert all(r["run_id"] == tel.run_id for r in built)
    early = _named(built, "before_logger_a")[0]
    late = _named(built, "after_logger")[0]
    assert early["epoch"] is None and early["step"] is None
    assert (late["epoch"], late["step"]) == (3, 0)
    # after finalize the recorder is handed back: the next logger's
    assert "after_finalize" not in names
    assert _named(programs.RECORDER.backlog(), "after_finalize")


def test_backlog_is_bounded_and_a_listener_never_raises_into_jax(
        monkeypatch):
    import jax.numpy as jnp

    from hydragnn_tpu.telemetry import programs

    rec = programs.ProgramRecorder(backlog=3)
    for i in range(5):
        rec.on_time_span(programs._TRACE, 10.0 + i, 10.1 + i,
                         fun_name=f"f{i}")
        rec.on_time_span(programs._LOWER, 10.1 + i, 10.2 + i,
                         fun_name=f"jit(f{i})")
        rec.on_event(programs._CACHE_ASKED)
        if i % 2:
            rec.on_event(programs._CACHE_HIT)
            rec.on_duration(programs._CACHE_LOAD, 0.25)
        rec.on_time_span(programs._BUILD, 10.2 + i, 10.7 + i,
                         fun_name=f"jit(f{i})")
    kept = rec.backlog()
    assert [r["name"] for r in kept] == ["f2", "f3", "f4"]
    assert [r["seq"] for r in kept] == [3, 4, 5]    # the gap: two dropped
    assert [r["cache"] for r in kept] == ["miss", "hit", "miss"]
    assert [r["cache_load_s"] for r in kept] == [0.0, 0.25, 0.0]
    assert kept[0]["t_start"] == 12.0 and kept[0]["t"] == 12.7
    assert kept[0]["trace_s"] == pytest.approx(0.1)
    assert kept[0]["build_s"] == pytest.approx(0.5)
    # a build that reports no trace or lowering of its own still counts
    rec.on_time_span(programs._BUILD, 20.0, 21.0, fun_name="jit(bare)")
    assert rec.backlog()[-1]["t_start"] == 20.0

    def broken(_rec):
        raise RuntimeError("a fault of the record")

    monkeypatch.setattr(programs.RECORDER, "_finish", broken)
    out = _fresh_jit("built_under_a_broken_recorder")(jnp.ones(3))
    assert float(out[0]) == pytest.approx(np.sin(1.0) * 3.0 + 1.0)


def _program_run(tmp_path, monkeypatch, loaders, name, **kw):
    """Two epochs of the real trainer with the JSONL sink and annotated
    regions (so ``StepPrograms`` writes); the run's records."""
    from test_resilience import _run

    from hydragnn_tpu.utils import tracer as tr

    from hydragnn_tpu.telemetry import programs

    monkeypatch.setenv("HYDRAGNN_RESIDENT_DATASET", "0")
    # what earlier tests of this process built waits in the backlog, and
    # this logger adopts it: the run's own builds come after
    seq_before = programs.RECORDER._seq
    tel = MetricsLogger(TelemetryConfig(enable=True, sinks=("jsonl",)),
                        run_name=name, out_dir=str(tmp_path / "tel"))
    tr.initialize(timer=True, jax_annotations=True)
    regions_before = tr.open_regions()
    try:
        _, hist = _run(loaders, tmp_path, name, num_epoch=2, telemetry=tel,
                       **kw)
    finally:
        tr.initialize()
    assert len(hist["train"]) == 2
    assert tr.open_regions() == regions_before
    recs = [json.loads(line) for line in open(tel.jsonl_path)]
    return [r for r in recs
            if r["event"] != "program" or r["seq"] > seq_before]


@pytest.mark.parametrize("use_mesh_dp, train_name, eval_name", [
    (False, "train_step", "eval_step"),
    (True, "train_step", "dp_eval_step"),
], ids=["local", "mesh_dp"])
def test_two_epochs_build_each_step_program_once_in_epoch_0(
        tmp_path, monkeypatch, use_mesh_dp, train_name, eval_name):
    from test_resilience import _Loaders

    monkeypatch.setenv("HYDRAGNN_STEPS_PER_DISPATCH", "1")
    recs = _program_run(
        tmp_path, monkeypatch,
        _Loaders(n_train=64, batch_size=4) if use_mesh_dp else _Loaders(),
        "builds_once", use_mesh_dp=use_mesh_dp)
    built = [r for r in recs if r["event"] == "program"]
    assert all(r["epoch"] in (0, None) for r in built), [
        (r["name"], r["region"], r["epoch"]) for r in built if r["epoch"]]
    for name, region in ((train_name, "train.dispatch"),
                         (eval_name, "eval.dispatch")):
        # one build to run it; the in-run MFU estimate's second compile
        # and the compile from shapes behind hlo_scopes.json say so
        mine = [r for r in _named(built, name) if r["region"] not in (
            "setup.mfu_cost", "telemetry.step_programs")]
        assert len(mine) == 1, (name, [r["region"] for r in built])
        assert mine[0]["region"] == region and mine[0]["epoch"] == 0
    assert _named(built, train_name)[0]["step"] == 0
    # every optimizer step of epoch 0 was dispatched before the first eval
    steps_0 = sum(r["steps_in_dispatch"] for r in recs
                  if r["event"] == "step" and r["epoch"] == 0)
    assert _named(built, eval_name)[0]["step"] == steps_0
    # the step programs state their memory once, in epoch 0's tail
    memory = [r for r in recs if r["event"] == "program_memory"]
    assert sorted(r["name"] for r in memory) == sorted(
        [f"jit_{train_name}", f"jit_{eval_name}"])
    t_epoch1 = min(r["t"] for r in recs
                   if r["event"] == "step" and r["epoch"] == 1)
    assert all(r["t"] < t_epoch1 for r in memory)


def test_a_rebuild_in_epoch_1_names_its_region_and_epoch(
        tmp_path, monkeypatch):
    """A batch shape that epoch 0 did not have: the train step is built
    again, and its record says where and when."""
    from test_resilience import _Loaders

    from hydragnn_tpu.graph.batch import PadSpec

    loaders = _Loaders()
    wide = PadSpec(loaders.pad.num_nodes + 8, loaders.pad.num_edges + 16,
                   loaders.pad.num_graphs)

    def with_a_new_shape():
        train_l, val_l, test_l = loaders()
        set_epoch = train_l.set_epoch

        def grows(epoch):
            set_epoch(epoch)
            if epoch >= 1:
                train_l.pad_spec, train_l.pad_specs = wide, [wide]

        train_l.set_epoch = grows
        return train_l, val_l, test_l

    monkeypatch.setenv("HYDRAGNN_STEPS_PER_DISPATCH", "1")
    recs = _program_run(tmp_path, monkeypatch, with_a_new_shape, "rebuilds")
    steps = _named(recs, "train_step")
    by_epoch = {r["epoch"]: r for r in steps
                if r["region"] == "train.dispatch"}
    assert set(by_epoch) == {0, 1}, [(r["region"], r["epoch"])
                                     for r in steps]
    assert by_epoch[1]["step"] == 4         # epoch 0's four steps are done
    assert by_epoch[0]["t"] < by_epoch[1]["t_start"]


def test_step_programs_hand_on_what_memory_analysis_says(tmp_path):
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.telemetry.hlo_scopes import _MEMORY_FIELDS, StepPrograms

    @jax.jit
    def step(x):
        return jnp.tanh(x @ x) * 2.0

    tel = MetricsLogger(TelemetryConfig(enable=True, sinks=("jsonl",)),
                        run_name="memory", out_dir=str(tmp_path / "tel"))
    notes = StepPrograms()
    x = jnp.ones((32, 32))
    notes.watch(step)(x)
    notes.write(str(tmp_path / "tel" / "hlo_scopes.json"),
                tel.log_program_memory)
    tel.finalize()
    recs = [json.loads(line) for line in open(tel.jsonl_path)]
    (mem,) = [r for r in recs if r["event"] == "program_memory"]
    said = step.lower(x).compile().memory_analysis()
    assert mem["name"] == "jit_step" and mem["run_id"] == tel.run_id
    for ours, theirs in _MEMORY_FIELDS:
        assert mem[ours] == getattr(said, theirs), ours
    assert mem["argument_bytes"] == mem["output_bytes"] == 32 * 32 * 4
    # the compile from shapes is itself a build, in the region of the write
    again = [r for r in _named(recs, "step")
             if r["region"] == "telemetry.step_programs"]
    assert len(again) <= 1


def test_step_lowers_the_same_with_the_listeners_or_without():
    """The build record listens; it is not in the program: the lowered
    StableHLO of the train step is the one a process without the
    listeners lowers (the pattern of the scopes' metadata-only test)."""
    import jax

    from test_resilience import _batch, _model

    from hydragnn_tpu.telemetry import programs
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.trainer import (
        create_train_state, make_train_step)

    cfg, model = _model()
    opt = select_optimizer({"type": "AdamW", "learning_rate": 1e-3})
    batch = _batch()
    state = jax.eval_shape(
        lambda b: create_train_state(model, b, opt), batch)

    def lowered():
        return jax.jit(make_train_step(
            model, cfg, opt, telemetry_metrics=True)).lower(
                state, batch).as_text()

    with_listeners = lowered()
    seq = programs.RECORDER._seq
    programs.uninstall()
    try:
        without = lowered()
        _fresh_jit("built_unheard")(jax.numpy.ones(2))
        assert programs.RECORDER._seq == seq       # nothing was heard
    finally:
        programs.install()
    assert with_listeners == without


def test_teleview_programs_table_flags_misses_and_rebuilds():
    from tools.teleview import programs_section

    def built(name, region, epoch, cache, build_s, load=0.0):
        return {"event": "program", "name": name, "region": region,
                "epoch": epoch, "step": 8 if epoch else 0, "cache": cache,
                "trace_s": 1.0, "lower_s": 0.5, "build_s": build_s,
                "cache_load_s": load}

    text = programs_section(
        [built("init", "setup.init_state", None, "hit", 0.4, load=0.3),
         built("convert_element_type", None, None, "hit", 0.2, load=0.1),
         built("scan_step", "train.dispatch", 0, "miss", 30.0),
         built("scan_step", "train.dispatch", 1, "miss", 31.0)],
        [{"event": "program_memory", "name": "jit_scan_step",
          "argument_bytes": 8 * 10 ** 9, "output_bytes": 8 * 10 ** 9,
          "alias_bytes": 8 * 10 ** 9, "temp_bytes": 6 * 10 ** 9,
          "generated_code_bytes": 0, "peak_bytes": 0}])
    lines = text.splitlines()
    row = {ln.split()[0]: ln.split()[1:] for ln in lines[2:6]}
    assert row["train.dispatch"] == ["2", "3.000", "61.000", "0.000"]
    assert row["setup.init_state"] == ["1", "1.500", "0.000", "0.300"]
    assert row["total"] == ["4", "6.000", "61.000", "0.400"]
    assert "2 cache miss(es), slowest first: scan_step (31.00s, " \
        "train.dispatch), scan_step (30.00s" in text
    warned = [ln for ln in lines if "WARNING rebuild" in ln]
    assert len(warned) == 1 and "epoch 1 step 8" in warned[0]
    assert "memory jit_scan_step: needs 14.000 GB a device" in text
