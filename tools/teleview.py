#!/usr/bin/env python
"""teleview: summarize a telemetry JSONL event log as a compact table.

Usage:
    python tools/teleview.py LOGDIR_OR_FILE [--tail N] [--epochs] [--json]

Accepts either the events.jsonl file itself or a directory containing one
(e.g. ``logs/<run>/telemetry``).  Pure stdlib — safe to run anywhere,
including while a run is still writing (the JSONL sink flushes per record).

Default view: the last ``--tail`` step records (epoch, step, loss,
grad-norm, step time, padding waste, MFU estimate) followed by the epoch
rows, the programs JAX built by the region that caused them (rebuilds
inside the steady state flagged) and the manifest summary.  ``--epochs`` shows only epoch rows;
``--json`` re-emits the selected records as JSONL (for piping into jq).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Any, Dict, List, Optional


def find_events(path: str) -> str:
    if os.path.isdir(path):
        cand = os.path.join(path, "events.jsonl")
        if os.path.exists(cand):
            return cand
        # accept logs/<run>/ by looking one level down
        cand = os.path.join(path, "telemetry", "events.jsonl")
        if os.path.exists(cand):
            return cand
        raise FileNotFoundError(f"no events.jsonl under {path}")
    return path


def load_records(path: str) -> List[Dict[str, Any]]:
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except json.JSONDecodeError:
                # a live run may be mid-write on the last line
                continue
    return records


def _fmt(v: Optional[float], spec: str = ".4g", dash: str = "-") -> str:
    if v is None:
        return dash
    return format(v, spec)


def _table(rows: List[List[str]], headers: List[str]) -> str:
    widths = [len(h) for h in headers]
    for r in rows:
        widths = [max(w, len(c)) for w, c in zip(widths, r)]
    fmt = "  ".join(f"{{:>{w}}}" for w in widths)
    lines = [fmt.format(*headers)]
    lines.append(fmt.format(*("-" * w for w in widths)))
    lines.extend(fmt.format(*r) for r in rows)
    return "\n".join(lines)


def step_rows(steps: List[Dict[str, Any]]) -> str:
    rows = []
    for r in steps:
        pad = r.get("padding") or {}
        rows.append([
            str(r.get("epoch", "-")),
            str(r.get("step", "-")),
            _fmt(r.get("loss"), ".6g"),
            _fmt(r.get("grad_norm")),
            _fmt(None if r.get("step_time_s") is None
                 else r["step_time_s"] * 1e3, ".3g"),
            _fmt(r.get("graphs_per_s"), ".4g"),
            _fmt(pad.get("nodes_waste_pct"), ".1f"),
            _fmt(pad.get("edges_waste_pct"), ".1f"),
            _fmt(r.get("mfu_est_pct"), ".3g"),
        ])
    return _table(rows, ["ep", "step", "loss", "|grad|", "ms",
                         "graphs/s", "pad_n%", "pad_e%", "mfu%"])


def pipeline_line(pipe: Dict[str, Any]) -> str:
    """The manifest's ``history.pipeline`` block on one line: the fast
    path the run took, and who shaped its train dispatch groups (the
    groups themselves, with the shapes, or the bucket ladder)."""
    shapes = ", ".join(f"{n}x{e} ({g} group{'s' if g != 1 else ''})"
                       for n, e, g in pipe.get("group_shapes") or [])
    return (f"pipeline: K={pipe.get('steps_per_dispatch')} "
            f"resident={pipe.get('resident')} "
            f"mesh_dp={pipe.get('use_mesh_dp')} "
            f"dp={pipe.get('dp_extent')}  group shapes (nodes x edges): "
            + (f"fitted {shapes or '(no plan made)'}"
               if pipe.get("group_fit") else "from the ladder"))


def health_section(health: List[Dict[str, Any]],
                   manifests: List[Dict[str, Any]]) -> str:
    """Resilience health events (docs/RESILIENCE.md): skipped steps,
    preemption saves, resumes, checkpoint retries.  Counts come from the
    manifest when one exists (it tallies even sink-less ranks' events),
    falling back to counting the health records themselves."""
    counts: Dict[str, int] = {}
    for m in manifests[-1:]:
        counts = dict(m.get("health") or {})
    if not counts:
        # no manifest (run killed before finalize): rebuild the tally from
        # the records; `count` carries multi-step events (K skipped steps
        # in one scanned dispatch emit a single record with count=K)
        for r in health:
            k = str(r.get("kind"))
            counts[k] = counts.get(k, 0) + int(r.get("count", 1) or 1)
    lines = ["  " + "  ".join(f"{k}={counts[k]}" for k in sorted(counts))]
    for r in health[-10:]:
        kind = r.get("kind")
        where = []
        for f in ("epoch", "step", "items", "attempt", "what", "ok",
                  "error", "consecutive",
                  # serving events (docs/SERVING.md)
                  "n", "reason", "fill_pct", "wait_ms", "predict_ms",
                  "depth", "port", "served",
                  # overload/reload events
                  "est_wait_ms", "deadline_ms", "waited_ms", "timeout_s",
                  "cooldown_s", "source", "golden_max_delta",
                  # fleet events (docs/SERVING.md "Replica fleet")
                  "replica", "replicas", "live", "total", "quorum",
                  "backoff_s", "restarts", "swapped", "rolled_back"):
            if r.get(f) is not None:
                where.append(f"{f}={r[f]}")
        lines.append(f"  {kind}: " + "  ".join(where))
    return "\n".join(lines)


# serving event kinds (docs/TELEMETRY.md "Serving events"): emitted by
# hydragnn_tpu/serve through the same MetricsLogger.health spine
_SERVING_KINDS = ("request_enqueued", "batch_flushed", "deadline_flush",
                  "cache_miss", "batch_error", "serve_start", "serve_drain",
                  # overload/robustness events (docs/SERVING.md
                  # "Overload behavior")
                  "request_shed", "deadline_expired", "predict_timeout",
                  "breaker_open", "breaker_half_open", "breaker_close",
                  "reload_ok", "reload_rollback")

# WARN when more than this fraction of offered requests were shed
# (request_shed + deadline_expired over offered = enqueued + shed)
_SHED_WARN_RATIO = 0.10

# WARN when a serving bucket's MEAN node-padding waste exceeds this —
# the signal that the ladder is mis-sized for the traffic and
# tools/buckettune.py should re-solve it
_BUCKET_WASTE_WARN_PCT = 50.0

# replica-fleet event kinds (docs/TELEMETRY.md "Fleet events"): emitted
# by serve/fleet.py (supervisor) and serve/router.py
_FLEET_KINDS = ("fleet_start", "replica_start", "replica_dead",
                "replica_restart", "replica_eject", "replica_readmit",
                "replica_drain", "rolling_reload_start",
                "rolling_reload_ok", "rolling_reload_rollback",
                "fleet_retry", "fleet_degraded", "fleet_empty")


def fleet_section(health: List[Dict[str, Any]],
                  manifests: List[Dict[str, Any]]) -> str:
    """Replica-fleet story: event counts plus the WARNINGs an operator
    acts on — replicas observed below quorum, a fleet that went EMPTY
    (503s were served), restart-storm ejections (a crash-looping
    replica needs attention), and rolling reloads that rolled back."""
    counts: Dict[str, int] = {}
    for m in manifests[-1:]:
        counts = {k: v for k, v in (m.get("health") or {}).items()
                  if k in _FLEET_KINDS}
    if not counts:
        for r in health:
            k = str(r.get("kind"))
            if k in _FLEET_KINDS:
                counts[k] = counts.get(k, 0) + int(r.get("count", 1) or 1)
    lines = ["  " + "  ".join(f"{k}={counts[k]}" for k in sorted(counts))]
    starts = [r for r in health if r.get("kind") == "fleet_start"]
    if starts:
        s = starts[-1]
        lines.append(f"  fleet: {s.get('replicas')} {s.get('mode', '')} "
                     f"replica(s), quorum {s.get('quorum')}")
    n_deg = counts.get("fleet_degraded", 0)
    if n_deg:
        last = [r for r in health if r.get("kind") == "fleet_degraded"][-1:]
        where = (f" (last: {last[0].get('live')}/{last[0].get('total')} "
                 f"live vs quorum {last[0].get('quorum')})") if last else ""
        lines.append(f"  WARNING replicas fell below quorum {n_deg} "
                     f"time(s){where} — the fleet served degraded; check "
                     "replica_dead/replica_eject reasons")
    n_empty = counts.get("fleet_empty", 0)
    if n_empty:
        lines.append(f"  WARNING the fleet went EMPTY {n_empty} time(s) — "
                     "clients saw 503s; every replica was dead/ejected "
                     "at once")
    storms = [r for r in health if r.get("kind") == "replica_eject"
              and r.get("reason") == "restart_storm"]
    if storms:
        which = sorted({int(r.get("replica", -1)) for r in storms})
        lines.append(f"  WARNING restart storm: replica(s) {which} were "
                     "marked FAILED after exceeding the restart cap — "
                     "they will not be restarted without operator action")
    n_rb = counts.get("rolling_reload_rollback", 0)
    if n_rb:
        lines.append(f"  WARNING {n_rb} rolling reload(s) rolled back — "
                     "a candidate failed validation on a replica "
                     f"(rolling_reload_ok: "
                     f"{counts.get('rolling_reload_ok', 0)})")
    return "\n".join(lines)


def serve_bucket_section(serve_steps: List[Dict[str, Any]]) -> str:
    """Per-bucket fill/padding table from the batcher's serve step
    records (the trainer-schema padding block, docs/TELEMETRY.md):
    which buckets traffic actually lands in and how much of each padded
    batch was waste — the at-a-glance input to bucket-ladder retuning
    (tools/buckettune.py)."""
    groups: Dict[tuple, Dict[str, float]] = {}
    for r in serve_steps:
        b = r.get("bucket") or {}
        pad = r.get("padding") or {}
        key = (int(b.get("graphs", 0)), int(b.get("nodes", 0)),
               int(b.get("edges", 0)))
        g = groups.setdefault(key, {"flushes": 0, "graphs": 0.0,
                                    "fill": 0.0, "pad_n": 0.0,
                                    "pad_e": 0.0})
        g["flushes"] += 1
        g["graphs"] += float(r.get("num_graphs", 0))
        g["fill"] += float(r.get("fill_pct", 0.0))
        g["pad_n"] += float(pad.get("nodes_waste_pct", 0.0))
        g["pad_e"] += float(pad.get("edges_waste_pct", 0.0))
    rows, warns = [], []
    for key in sorted(groups):
        g = groups[key]
        n = max(int(g["flushes"]), 1)
        mean_pad_n = g["pad_n"] / n
        rows.append([
            f"{key[0]}g/{key[1]}n/{key[2]}e",
            str(int(g["flushes"])),
            str(int(g["graphs"])),
            f"{g['fill'] / n:.1f}",
            f"{mean_pad_n:.1f}",
            f"{g['pad_e'] / n:.1f}",
        ])
        if mean_pad_n > _BUCKET_WASTE_WARN_PCT:
            warns.append(
                f"  WARNING bucket {key[0]}g/{key[1]}n/{key[2]}e mean "
                f"node-padding waste {mean_pad_n:.1f}% exceeds "
                f"{_BUCKET_WASTE_WARN_PCT:.0f}% — re-solve the ladder "
                "with tools/buckettune.py")
    table = _table(rows, ["bucket", "flushes", "graphs", "fill%",
                          "pad_n%", "pad_e%"])
    out = "\n".join("  " + line for line in table.splitlines())
    if warns:
        out += "\n" + "\n".join(warns)
    return out


def serving_section(health: List[Dict[str, Any]],
                    manifests: List[Dict[str, Any]]) -> str:
    """Derived serving stats: event counts plus batch fill %, padding %,
    wait/predict times averaged over the batch_flushed records, and the
    deadline-vs-full flush split — the at-a-glance answer to "is the
    batcher filling buckets or timing out, and did anything recompile"."""
    counts: Dict[str, int] = {}
    for m in manifests[-1:]:
        counts = {k: v for k, v in (m.get("health") or {}).items()
                  if k in _SERVING_KINDS}
    if not counts:
        for r in health:
            k = str(r.get("kind"))
            if k in _SERVING_KINDS:
                counts[k] = counts.get(k, 0) + int(r.get("count", 1) or 1)
    lines = ["  " + "  ".join(f"{k}={counts[k]}" for k in sorted(counts))]
    flushed = [r for r in health if r.get("kind") == "batch_flushed"]
    if flushed:
        def _avg(key):
            vals = [float(r[key]) for r in flushed if r.get(key) is not None]
            return sum(vals) / len(vals) if vals else 0.0

        n_deadline = sum(1 for r in flushed if r.get("reason") == "deadline")
        lines.append(
            f"  batches {len(flushed)}  "
            f"fill {_avg('fill_pct'):.1f}%  pad_n {_avg('pad_nodes_pct'):.1f}%  "
            f"wait {_avg('wait_ms'):.2f}ms  predict {_avg('predict_ms'):.2f}ms  "
            f"deadline-flush {100.0 * n_deadline / len(flushed):.0f}%")
    n_miss = counts.get("cache_miss", 0)
    if n_miss:
        lines.append(f"  WARNING {n_miss} steady-state compile(s) — a "
                     "request shape missed the warmed bucket ladder")
    # overload accounting: shed ratio over OFFERED requests (accepted +
    # shed-at-admission; expired entries were accepted, then died in
    # the queue)
    n_shed = counts.get("request_shed", 0) + counts.get(
        "deadline_expired", 0)
    offered = counts.get("request_enqueued", 0) + counts.get(
        "request_shed", 0)
    if n_shed and offered:
        ratio = n_shed / offered
        lines.append(f"  shed {n_shed}/{offered} offered "
                     f"({100.0 * ratio:.1f}%: "
                     f"{counts.get('request_shed', 0)} at admission, "
                     f"{counts.get('deadline_expired', 0)} expired in "
                     "queue)")
        if ratio > _SHED_WARN_RATIO:
            lines.append(f"  WARNING shed ratio {100.0 * ratio:.1f}% "
                         f"exceeds {100.0 * _SHED_WARN_RATIO:.0f}% — the "
                         "server is overloaded (raise capacity, lower "
                         "deadlines, or add replicas)")
    n_open = counts.get("breaker_open", 0)
    if n_open:
        closes = counts.get("breaker_close", 0)
        state = "recovered" if closes >= n_open else "possibly still open"
        lines.append(f"  WARNING circuit breaker opened {n_open} time(s), "
                     f"closed {closes} ({state}) — see predict_timeout/"
                     "batch_error events")
    n_rb = counts.get("reload_rollback", 0)
    if n_rb:
        lines.append(f"  WARNING {n_rb} checkpoint reload rollback(s) — "
                     "a candidate failed validation or tripped the "
                     "breaker (reload_ok: "
                     f"{counts.get('reload_ok', 0)})")
    return "\n".join(lines)


def _mb(v: Optional[float]) -> str:
    # decimal MB: the same divisor bench.py --zero and docs/SCALING.md use,
    # so cross-checking this section against BENCH_zero.json lines up
    return "-" if v is None else f"{float(v) / 1e6:.2f} MB"


def sharding_section(shardings: List[Dict[str, Any]],
                     manifests: List[Dict[str, Any]]) -> str:
    """ZeRO sharding layout (docs/SCALING.md §4): effective stage, axis
    size, per-device resident param/opt bytes vs the replicated
    equivalents, padded-slice waste — and a WARNING when ZeRO was
    requested but the run fell back to replicated."""
    s: Dict[str, Any] = {}
    for m in manifests[-1:]:
        s = dict(m.get("sharding") or {})
    if not s and shardings:
        s = dict(shardings[-1])
    if not s:
        return "  (no sharding record)"
    stage = int(s.get("zero_stage", 0) or 0)
    req = int(s.get("zero_stage_requested", stage) or 0)
    lines = [f"  zero_stage={stage} (requested {req})  "
             f"axis={s.get('axis')} x{s.get('axis_size', 1)}"]
    pr, pd_ = s.get("param_bytes_replicated"), s.get("param_bytes_per_device")
    orp, od = s.get("opt_bytes_replicated"), s.get("opt_bytes_per_device")
    if od is not None:
        def _ratio(dev, repl):
            return (f" ({float(repl) / float(dev):.1f}x saving)"
                    if dev and repl and repl > dev else "")

        lines.append(
            f"  params {_mb(pd_)}/device (replicated {_mb(pr)}"
            f"{_ratio(pd_, pr)})  opt state {_mb(od)}/device "
            f"(replicated {_mb(orp)}{_ratio(od, orp)})")
        waste = s.get("padded_waste_bytes_per_device")
        if waste:
            lines.append(f"  padded-slice waste {_mb(waste)}/device")
    if req > stage:
        lines.append(
            f"  WARNING ZeRO stage {req} was requested but the run fell "
            f"back to replicated"
            + (f" ({s['fallback']})" if s.get("fallback") else "")
            + " — optimizer state is NOT sharded")
    gs = s.get("graph_shard") or {}
    if gs:
        lines.append(
            f"  graph_shard={gs.get('backend')} "
            f"(requested {gs.get('requested', gs.get('backend'))})  "
            f"shards={gs.get('n_shards', '-')} "
            f"method={gs.get('method', '-')} hops={gs.get('hops', '-')}")
        if gs.get("n_local") is not None:
            lines.append(
                f"  partition: {gs.get('n_nodes_real', '-')} nodes -> "
                f"{gs.get('n_local')} local rows/shard + "
                f"{gs.get('halo_rows_max', 0)} halo rows max "
                f"(buffer {gs.get('n_shards', 0)}x{gs.get('halo_pair', 0)}"
                f"/peer, {gs.get('halo_waste_pct', 0)}% padding waste)  "
                f"cut edges {gs.get('cut_edge_pct', '-')}%")
        imb = max(float(gs.get("node_imbalance", 1.0) or 1.0),
                  float(gs.get("edge_imbalance", 1.0) or 1.0))
        if imb > 1.5:
            lines.append(
                f"  WARNING partition imbalance {imb:.2f}x (max/mean "
                "owned rows or edges) — the slowest shard paces every "
                "step; try graph_shard_method=bfs|sfc or fewer shards")
        if gs.get("fallback"):
            lines.append(
                f"  WARNING graph sharding ({gs.get('requested')}) was "
                f"requested but the run fell back ({gs['fallback']}) — "
                "the graph must fit ONE device")
        if gs.get("backend") == "gspmd":
            lines.append(
                "  NOTE gspmd is the correctness baseline: GSPMD "
                "all-gathers the full node array per step — no memory "
                "headroom over single-device (docs/SCALING.md §6)")
    return "\n".join(lines)


def _quantile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank quantile over an ASCENDING list (same definition as
    hydragnn_tpu/telemetry/trace.py — teleview stays stdlib-only, so the
    three lines are duplicated rather than importing the jax-adjacent
    package)."""
    if not sorted_vals:
        return 0.0
    idx = max(0, min(len(sorted_vals) - 1,
                     int(round(q * (len(sorted_vals) - 1)))))
    return sorted_vals[idx]


def _span_family(name: str) -> str:
    return name.split(".", 1)[0] if "." in name else name


def chrome_trace_doc(spans: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Chrome-trace JSON (chrome://tracing / Perfetto "load trace") from
    span records: complete events (ph=X, µs), one pid per name family
    (serve/train/comm), one tid per trace_id so each request reads as a
    lane.  Mirrors hydragnn_tpu.telemetry.trace.chrome_trace."""
    tids: Dict[str, int] = {}
    events = []
    for r in spans:
        tid = tids.setdefault(str(r.get("trace_id", "")), len(tids) + 1)
        args = {k: v for k, v in r.items()
                if k not in ("event", "name", "t_start_s", "dur_ms",
                             "run_id", "rank", "t")}
        events.append({
            "name": r.get("name", "?"),
            "cat": _span_family(str(r.get("name", "?"))),
            "ph": "X",
            "ts": round(float(r.get("t_start_s", 0.0)) * 1e6, 1),
            "dur": round(float(r.get("dur_ms", 0.0)) * 1e3, 1),
            "pid": _span_family(str(r.get("name", "?"))),
            "tid": tid,
            "args": args,
        })
    return {"traceEvents": events, "displayTimeUnit": "ms"}


def trace_section(spans: List[Dict[str, Any]], tail: int = 3) -> str:
    """Flight-recorder view: per-name duration percentiles, then a text
    waterfall of the last ``tail`` traces (request span with its linked
    flush/queue/pad/predict children indented under it) — and the WARN
    the percentiles exist for: queue-wait p99 above predict p99 means
    requests spend longer WAITING than computing (the batcher, not the
    model, is the bottleneck — grow capacity or shrink max_wait_ms)."""
    by_name: Dict[str, List[float]] = {}
    for r in spans:
        by_name.setdefault(str(r.get("name", "?")), []).append(
            float(r.get("dur_ms", 0.0)))
    rows = []
    p99s: Dict[str, float] = {}
    for name in sorted(by_name):
        vals = sorted(by_name[name])
        p99s[name] = _quantile(vals, 0.99)
        rows.append([name, str(len(vals)),
                     f"{_quantile(vals, 0.5):.3f}",
                     f"{_quantile(vals, 0.95):.3f}",
                     f"{p99s[name]:.3f}", f"{vals[-1]:.3f}"])
    table = _table(rows, ["span", "count", "p50ms", "p95ms", "p99ms",
                          "maxms"])
    lines = ["  " + ln for ln in table.splitlines()]

    qw, pr = p99s.get("serve.queue_wait"), p99s.get("serve.predict")
    if qw is not None and pr is not None and qw > pr:
        lines.append(
            f"  WARNING queue-wait p99 {qw:.3f}ms exceeds predict p99 "
            f"{pr:.3f}ms — requests wait longer than they compute; the "
            "batcher is the bottleneck (add replicas, lower max_wait_ms, "
            "or widen buckets)")

    # waterfall: group by trace_id, children indented under their parent
    by_trace: Dict[str, List[Dict[str, Any]]] = {}
    order: List[str] = []
    for r in spans:
        t = str(r.get("trace_id", ""))
        if t not in by_trace:
            order.append(t)
        by_trace.setdefault(t, []).append(r)
    # flush spans live in their own trace and LINK the request traces
    # they carried — fold linked traces into the flush's waterfall view
    for t in order[-tail:]:
        group = sorted(by_trace[t],
                       key=lambda r: float(r.get("t_start_s", 0.0)))
        lines.append(f"  trace {t[:16]}…" if len(t) > 16
                     else f"  trace {t}")
        ids = {str(r.get("span_id", "")) for r in group}
        t0 = float(group[0].get("t_start_s", 0.0))
        for r in group:
            indent = "    " if str(r.get("parent_id", "")) in ids else "  "
            off = (float(r.get("t_start_s", 0.0)) - t0) * 1e3
            extra = ""
            if r.get("links"):
                extra = f"  links={len(r['links'])} request(s)"
            if r.get("status") is not None:
                extra += f"  status={r['status']}"
            lines.append(f"  {indent}+{off:8.3f}ms  "
                         f"{r.get('name', '?'):<18} "
                         f"{float(r.get('dur_ms', 0.0)):9.3f}ms{extra}")
    return "\n".join(lines)


def programs_section(programs: List[Dict[str, Any]],
                     memory: List[Dict[str, Any]]) -> str:
    """The programs JAX built (``event: "program"``, telemetry/programs.py)
    by the region that caused them: how many, and what tracing + lowering,
    compiling and the compile cache's reads cost; every cache miss by
    name; a WARNING for every build in epoch >= 1 (a REBUILD inside the
    steady state: a new batch shape, a changed static argument); and what
    the compiler says each step program needs on a device
    (``program_memory``)."""
    by_region: Dict[str, List[float]] = {}
    for r in programs:
        row = by_region.setdefault(str(r.get("region") or "-"),
                                   [0, 0.0, 0.0, 0.0])
        row[0] += 1
        row[1] += float(r.get("trace_s", 0.0)) + float(r.get("lower_s", 0.0))
        if r.get("cache") == "hit":
            row[3] += float(r.get("cache_load_s", 0.0))
        else:
            row[2] += (float(r.get("build_s", 0.0))
                       - float(r.get("cache_load_s", 0.0)))
    rows = [[region, str(int(n)), f"{tl:.3f}", f"{comp:.3f}", f"{load:.3f}"]
            for region, (n, tl, comp, load) in sorted(
                by_region.items(), key=lambda kv: -sum(kv[1][1:]))]
    total = [sum(v[i] for v in by_region.values()) for i in range(4)]
    rows.append(["total", str(int(total[0]))]
                + [f"{v:.3f}" for v in total[1:]])
    lines = [_table(rows, ["region", "built", "trace+lower s", "compile s",
                           "cache load s"])]
    missed = [r for r in programs if r.get("cache") == "miss"]
    if missed:
        lines.append(f"  {len(missed)} cache miss(es), slowest first: "
                     + ", ".join(
                         f"{r.get('name')} ({float(r.get('build_s', 0)):.2f}s"
                         f", {r.get('region') or '-'})"
                         for r in sorted(
                             missed, key=lambda r: -float(
                                 r.get("build_s", 0.0)))[:12]))
    for r in programs:
        if (r.get("epoch") or 0) >= 1:
            took = sum(float(r.get(k, 0.0))
                       for k in ("trace_s", "lower_s", "build_s"))
            lines.append(
                f"  WARNING rebuild: {r.get('name')} in "
                f"{r.get('region') or '-'}, epoch {r.get('epoch')} step "
                f"{r.get('step')}: cache {r.get('cache')}, {took:.2f}s")
    for r in memory:
        need = (r.get("argument_bytes", 0) + r.get("output_bytes", 0)
                - r.get("alias_bytes", 0) + r.get("temp_bytes", 0)
                + r.get("generated_code_bytes", 0))
        lines.append(
            f"  memory {r.get('name')}: needs {need / 1e9:.3f} GB a device"
            f" = arguments {r.get('argument_bytes', 0) / 1e9:.3f} + outputs"
            f" {r.get('output_bytes', 0) / 1e9:.3f} - aliased "
            f"{r.get('alias_bytes', 0) / 1e9:.3f} + temporaries "
            f"{r.get('temp_bytes', 0) / 1e9:.3f} + code "
            f"{r.get('generated_code_bytes', 0) / 1e9:.3f}")
    return "\n".join(lines)


def epoch_rows(epochs: List[Dict[str, Any]]) -> str:
    rows = []
    for r in epochs:
        rows.append([
            str(r.get("epoch", "-")),
            _fmt(r.get("train_loss"), ".6g"),
            _fmt(r.get("val_loss"), ".6g"),
            _fmt(r.get("test_loss"), ".6g"),
            _fmt(r.get("lr"), ".2e"),
            _fmt(r.get("epoch_time_s"), ".3g"),
            _fmt(r.get("padding_waste_pct"), ".1f"),
        ])
    return _table(rows, ["ep", "train", "val", "test", "lr", "s", "pad%"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("path", help="events.jsonl or a directory holding one")
    ap.add_argument("--tail", type=int, default=20,
                    help="show the last N step records (default 20)")
    ap.add_argument("--epochs", action="store_true",
                    help="epoch rows only")
    ap.add_argument("--json", action="store_true",
                    help="re-emit selected records as JSONL")
    ap.add_argument("--bench", default=None,
                    help="BENCH_evidence.json from a bench run: render "
                         "the --dense acceptance bound (MFU floor + "
                         "fused-dispatch check) as WARNINGs")
    ap.add_argument("--trace", action="store_true",
                    help="flight-recorder view: span percentiles + a "
                         "waterfall of the last traces (event=span "
                         "records; enable with HYDRAGNN_TRACE=1)")
    ap.add_argument("--chrome", default=None, metavar="OUT.json",
                    help="with --trace: also export the spans as a "
                         "Chrome-trace file (chrome://tracing, Perfetto)")
    args = ap.parse_args(argv)

    path = find_events(args.path)
    records = load_records(path)
    # serving flushes share the step-record schema (source: "serve") —
    # keep them out of the trainer step table
    steps = [r for r in records if r.get("event") == "step"
             and r.get("source") != "serve"]
    serve_steps = [r for r in records if r.get("event") == "step"
                   and r.get("source") == "serve"]
    epochs = [r for r in records if r.get("event") == "epoch"]
    manifests = [r for r in records if r.get("event") == "manifest"]
    health = [r for r in records if r.get("event") == "health"]
    shardings = [r for r in records if r.get("event") == "sharding"]
    spans = [r for r in records if r.get("event") == "span"]
    programs = [r for r in records if r.get("event") == "program"]
    memory = [r for r in records if r.get("event") == "program_memory"]

    if args.trace:
        if not spans:
            print(f"{path}: no span records — enable the flight recorder "
                  "with HYDRAGNN_TRACE=1 (Telemetry.trace)")
            return 0
        print(f"{path}: {len(spans)} span record(s)")
        print(trace_section(spans))
        comms = next((m.get("comms") for m in reversed(manifests)
                      if m.get("comms")), None)
        if comms:
            print(f"\ncomms (A/B probe, {comms.get('path', '?')} path): "
                  f"step {comms.get('step_ms', 0)}ms = "
                  f"compute {comms.get('compute_ms', 0)}ms + "
                  f"comm {comms.get('comm_ms', 0)}ms "
                  f"({comms.get('comm_pct', 0)}%)")
        if args.chrome:
            doc = chrome_trace_doc(spans)
            tmp = args.chrome + ".tmp"
            with open(tmp, "w") as f:
                json.dump(doc, f)
            os.replace(tmp, args.chrome)
            print(f"\nwrote {args.chrome} "
                  f"({len(doc['traceEvents'])} events) — load in "
                  "chrome://tracing or https://ui.perfetto.dev")
        return 0

    if args.json:
        sel = epochs if args.epochs else steps[-args.tail:] + epochs
        for r in sel:
            print(json.dumps(r, separators=(",", ":")))
        return 0

    print(f"{path}: {len(steps)} step, {len(epochs)} epoch, "
          f"{len(health)} health, {len(manifests)} manifest record(s)")
    if steps and not args.epochs:
        print("\nlast steps:")
        print(step_rows(steps[-args.tail:]))
    if epochs:
        print("\nepochs:")
        print(epoch_rows(epochs))
    if health or any(m.get("health") for m in manifests):
        print("\nhealth:")
        print(health_section(health, manifests))
    if shardings or any(m.get("sharding") for m in manifests):
        print("\nsharding:")
        print(sharding_section(shardings, manifests))
    if programs or memory:
        print("\nprograms:")
        print(programs_section(programs, memory))
    if any(r.get("kind") in _SERVING_KINDS for r in health) or any(
            k in _SERVING_KINDS for m in manifests
            for k in (m.get("health") or {})):
        print("\nserving:")
        print(serving_section(health, manifests))
    if any(r.get("kind") in _FLEET_KINDS for r in health) or any(
            k in _FLEET_KINDS for m in manifests
            for k in (m.get("health") or {})):
        print("\nfleet:")
        print(fleet_section(health, manifests))
    if serve_steps:
        print("\nserving buckets:")
        print(serve_bucket_section(serve_steps))
    if manifests:
        m = manifests[-1]
        peak = m.get("peak_flops_basis")
        print(f"\nmanifest: run {m.get('run_id')}  "
              f"steps {m.get('total_steps')}  "
              f"device {m.get('device_kind', '?')} x{m.get('device_count', '?')}  "
              + (f"peak basis {peak / 1e12:.0f} TF/s" if peak
                 else "peak basis: none (device not in DEVICE_PEAKS)"))
        pipe = (m.get("history") or {}).get("pipeline")
        if pipe:
            print(f"  {pipeline_line(pipe)}")
        agg = (m.get("ring_summary") or {}).get("mfu_est_pct")
        if agg:
            print(f"  mfu_est_pct (ring window): avg {agg['avg']:.3g}  "
                  f"min {agg['min']:.3g}  max {agg['max']:.3g}")
        disp = m.get("aggr_dispatch") or {}
        if disp:
            fused = sum(v for k, v in disp.items() if k.endswith(":fused"))
            fallback = sum(v for k, v in disp.items()
                           if k.endswith(":scatter"))
            summary = m.get("aggr_dispatch_summary", "")
            print(f"  aggr dispatch: {int(fused)} fused / {int(fallback)} "
                  f"scatter-fallback ({summary})")
            fell = sorted(k for k in disp if k.endswith(":scatter"))
            # the silent-fallback signal this tally exists for: warn on
            # ANY :scatter entry when the run either asked for the fused
            # backend (run_start records it) or did reach it elsewhere —
            # a run that fell ENTIRELY off the fast path is the worst
            # case, not an exempt one
            # match the run_start belonging to THIS manifest (append-mode
            # JSONL can hold several runs; a prior fused run must not
            # make a deliberate scatter run warn)
            starts = [r for r in records if r.get("event") == "run_start"
                      and r.get("run_id") == m.get("run_id")]
            if not starts:
                starts = [r for r in records
                          if r.get("event") == "run_start"][-1:]
            want_fused = any(r.get("aggr_backend") == "fused"
                             for r in starts)
            if fell and (fused or want_fused):
                print("  WARNING fell off the fast path: "
                      + ", ".join(f"{k}={disp[k]}" for k in fell))
        timers = m.get("timers") or {}
        for name, s in sorted(timers.items()):
            print(f"  timer {name}: {s.get('total_s', 0.0):.3f}s "
                  f"over {int(s.get('count', 0))} calls")
    if args.bench:
        # the SAME bound `bench.py --dense` exits 1 on, rendered as
        # WARNINGs (teleview never fails a pipeline — it narrates one)
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        import bench as _bench

        with open(args.bench) as f:
            ev = json.load(f)
        ok, failures, table = _bench.dense_gate(ev)
        floors = ", ".join(
            f"{k} ≥{v}%" for k, v in sorted(
                _bench.DENSE_MFU_FLOORS.items()))
        print(f"\ndense gate ({args.bench}): "
              f"MFU floors {floors} (else ≥{_bench.DENSE_MFU_FLOOR}%), "
              "fused dispatch on "
              + "/".join(_bench.MAINLINE_FUSED_ARCHS))
        for row in table:
            if row["kind"] == "dense":
                print(f"  rung {row['name']}: {row['mfu_pct']}% MFU "
                      f"(floor {row['mfu_floor']}%)  "
                      f"{row['graphs_per_sec']} g/s")
            else:
                print(f"  arch {row['name']}: {row['graphs_per_sec']} g/s"
                      f"  aggr={row['aggr_backend']}")
        for fmsg in failures:
            print(f"  WARNING {fmsg}")
        if ok:
            print("  PASS every bound held")
    return 0


if __name__ == "__main__":
    sys.exit(main())
