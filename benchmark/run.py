#!/usr/bin/env python3
"""The benchmark's command: one process, one cell, once.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

It knows no cell, configuration, traffic mix, driver or metric by name.
``BENCHMARK.json`` names them; each is a file found by that name:

    configs/<config>.json        the configuration as it is run
    traffic/<mix>.json           driver, env, overrides, expect, why
    drivers/<driver>.py          run(ctx) -> correct / attempted / failed /
                                 end_to_end / facts
    corpora/<generator>.py       generate(n, seed, params), to_samples(...)
    layer_metrics/<metric>.py    read(facts) -> value or None

so a later PR adds a cell, a configuration, a mix, a driver or a layer
metric as new files plus entries and edits nothing that is here
(tests/benchmark/test_add_by_file.py).

The platform must be ``tpu`` and the device count the cell's ``chips``;
otherwise the exit code is non-zero and no result is printed.  The only
CPU mode is the explicit ``--dry-cpu`` rehearsal: the configuration's own
``dry_cpu`` overrides shrink it, Pallas runs interpreted, the line says
``"platform": "cpu"`` and carries only counts (metrics whose source is
``program_counter``) — no rate, no time, no idle share, no memory figure.

The LAST stdout line is the result object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``.  With
``--trace 0`` the metrics are the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics.
"""

from __future__ import annotations

import time

_T_START = time.monotonic()          # set-up counts from here

import argparse  # noqa: E402
import hashlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CORPUS_CACHE_KEEP = 4


def load_module(kind: str, name: str):
    """The plug-in ``<kind>/<name>.py`` (or ``<name>.py`` beside this file
    for ``kind == ""``), loaded by path: nothing is registered anywhere."""
    path = os.path.join(HERE, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"benchmark: no such file {path}")
    mod_name = "benchmark_" + (f"{kind}_{name}" if kind else name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def deep_merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = (deep_merge(out[k], v)
                  if isinstance(v, dict) and isinstance(out.get(k), dict)
                  else v)
    return out


def applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def corpus_samples(corpus_cfg: dict, seed: int, config: dict, say):
    """The configuration's corpus for ``seed`` as GraphSamples.  The flat
    arrays are kept as ONE file under ``.cache/corpus`` (newest
    CORPUS_CACHE_KEEP kept), keyed by generator, size, seed and params."""
    import numpy as np

    gen = load_module("corpora", corpus_cfg["generator"])
    key = hashlib.sha256(json.dumps(
        [corpus_cfg["generator"], corpus_cfg["n"], seed,
         corpus_cfg.get("params", {})], sort_keys=True).encode()
    ).hexdigest()[:16]
    cache_dir = os.path.join(HERE, ".cache", "corpus")
    path = os.path.join(cache_dir, f"{corpus_cfg['generator']}-{key}.npz")
    if os.path.isfile(path):
        with np.load(path) as z:
            corpus = {k: z[k] for k in z.files}
        say(f"corpus: read {path}")
    else:
        corpus = gen.generate(int(corpus_cfg["n"]), seed,
                              corpus_cfg.get("params", {}))
        os.makedirs(cache_dir, exist_ok=True)
        tmp = path + ".tmp.npz"
        np.savez(tmp, **corpus)
        os.replace(tmp, path)
        old = sorted((os.path.join(cache_dir, f)
                      for f in os.listdir(cache_dir) if f.endswith(".npz")),
                     key=os.path.getmtime)[:-CORPUS_CACHE_KEEP]
        for f in old:
            os.remove(f)
    return gen.to_samples(corpus, config)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dry-cpu", action="store_true",
                    help="CPU rehearsal at the configuration's dry_cpu "
                         "sizes; prints counts only")
    args = ap.parse_args(argv)

    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        print(f"benchmark: no workload {args.workload!r} in BENCHMARK.json "
              f"(have: {sorted(cells)})", file=sys.stderr)
        return 2
    cell = cells[args.workload]
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     f"{cell['traffic']}.json"))
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])

    if args.dry_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
        # a rehearsal neither reads nor leaves compiled programs
        os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
        if cell["chips"] > 1:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + f" --xla_force_host_platform_device_count={cell['chips']}"
            ).strip()
        config = deep_merge(config, config.get("dry_cpu", {}))
    config = deep_merge(config, traffic.get("config_overrides", {}))
    for k, v in traffic.get("env", {}).items():
        os.environ[k] = str(v)

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    try:
        import hydragnn_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the system under test is not in this checkout "
              f"({e}); nothing to measure", file=sys.stderr)
        return 2
    import jax

    devs = jax.devices()
    platform, kind, count = devs[0].platform, devs[0].device_kind, len(devs)
    want = "cpu" if args.dry_cpu else "tpu"
    if platform != want or count != cell["chips"]:
        # the chip is never hidden: nothing on stdout, a non-zero exit
        print(f"benchmark: cell {cell['name']} needs {cell['chips']} x "
              f"{want}, JAX reports {count} x {platform} ({kind}); "
              "refusing to run", file=sys.stderr)
        return 3
    if not args.dry_cpu:
        load_module("", "peaks").peaks_for(kind)   # unknown chip: an error

    def say(msg: str) -> None:
        print(f"[{platform}] {msg}", flush=True)

    say(f"cell={cell['name']} config={cell['config']} "
        f"traffic={cell['traffic']} chips={count} kind={kind} "
        f"seed={args.seed} seconds={seconds:g} trace={args.trace}")
    driver = load_module("drivers", traffic["driver"])
    ctx = {
        "t_start": _T_START, "cell": cell, "config": config,
        "traffic": traffic, "seed": args.seed, "seconds": seconds,
        "trace": bool(args.trace), "dry": args.dry_cpu, "say": say,
        "workdir": os.path.join(HERE, ".cache", "runs", cell["name"]),
        "corpus": lambda c, s, cfg: corpus_samples(c, s, cfg, say),
    }
    result = driver.run(ctx)
    facts = result["facts"]

    values = {}
    if args.trace:
        try:
            facts["trace"] = load_module("", "trace_reduce").reduce_run(facts)
        except Exception:  # an unreadable trace: its metrics are left out
            import traceback

            traceback.print_exc()
            facts["trace"] = None
        if facts.get("trace_error"):
            say(f"profiler: {facts['trace_error']}")
        for m in bench["per_layer"]:
            if not applies(m, cell["name"]):
                continue
            if args.dry_cpu and m["source"] != "program_counter":
                continue
            v = load_module("layer_metrics", m["name"]).read(facts)
            if v is not None:
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}
        e2e = {k: v for k, v in result["end_to_end"].items() if v is not None}
        say(f"traced run's own end-to-end numbers (tracing overhead shows "
            f"against the untraced runs): {json.dumps(e2e)}")
    elif not args.dry_cpu:
        for m in bench["end_to_end"]:
            v = result["end_to_end"].get(m["name"])
            if applies(m, cell["name"]) and v is not None:
                values[m["name"]] = {"value": float(v), "unit": m["unit"]}

    device = {"platform": platform, "kind": kind, "count": count}
    line = {"correct": result["correct"], "attempted": result["attempted"],
            "failed": result["failed"], "metrics": values, "device": device}
    if not args.dry_cpu:
        device["memory_peak_bytes"] = facts["memory_peak_bytes"]
        tr = facts.get("trace")
        if args.trace and tr:
            device["busy_s"] = tr["busy_s"]
            device["window_s"] = tr["window_s"]
            line["breakdown"] = {
                "device_ops": [[n, t] for n, t in tr["device_ops"]],
                "idle_gaps": [[n, t] for n, t in tr["idle_gaps"]]}
    for m, v in values.items():
        say(f"{m} = {v['value']:.6g} {v['unit']}")
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
