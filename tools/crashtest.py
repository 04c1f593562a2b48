#!/usr/bin/env python
"""crashtest: one-command kill-and-resume harness with a parity verdict.

Proves the resilience contract end-to-end with a REAL signal: spawn a
training run, SIGTERM it mid-epoch (the preemption handler saves a resume
bundle and exits gracefully), resume it from the bundle, and compare the
final params bit-for-bit against an uninterrupted run of the same config.

Usage:
    python tools/crashtest.py [--workdir DIR] [--epochs 6]
        [--kill-delay 1.0]     seconds after the first epoch line to SIGTERM
        [--chaos-step K]       deterministic injected preemption at train
                               dispatch K instead of a wall-clock SIGTERM
        [--mesh]               run the mesh-DP path (local devices)
        [--stream]             feed training from a gpack store through the
                               streaming data plane (data/stream/): the
                               resume child fast-forwards INSIDE the
                               stream plan instead of iterate-and-discard,
                               proving the skip-first-N path keeps mid-
                               epoch bit parity
        [--zero N]             ZeRO stage (1 or 2; implies --mesh): the
                               victim's optimizer state (and stage-2
                               params) train SHARDED, the resume bundle is
                               consolidated on save and re-sharded on load
                               — proving the PR-3 bit-parity guarantee
                               survives the shard/consolidate round trip
        [--elastic]            ELASTIC matrix (docs/RESILIENCE.md "Elastic
                               training"): kill a 4-device victim
                               mid-epoch, resume at 3 and at 5 devices
                               with the global batch preserved, across
                               zero_stage 0/1/2 plus one streaming combo.
                               Each combo proves three things: (1) the
                               consolidated bundle survives a reshard
                               round trip at the NEW device count
                               bit-for-bit; (2) the strict default policy
                               REFUSES the resize loudly; (3) under
                               Training.elastic_resume: epoch the resumed
                               run's loss trajectory matches an
                               uninterrupted fixed-size run at the new
                               count within FP-regroup tolerance
                               (--elastic-rtol; bit-identity across
                               different batch regroupings is not a thing
                               floating point offers)

Exit code 0 and "PARITY PASS" when the resumed run's params are identical
to the uninterrupted run's; non-zero otherwise.  Runs anywhere (CPU ok);
each phase is a subprocess so the victim really dies and the resume really
starts from a cold process (fresh jit caches, fresh orbax managers).
"""

from __future__ import annotations

import argparse
import os
import pickle
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# child: one training phase (baseline | victim | resume)
# ---------------------------------------------------------------------------


def _build(n_train: int, batch_size: int, epochs: int, mesh: bool,
           stream: bool = False, workdir: str = ""):
    import numpy as np

    from hydragnn_tpu.data.dataloader import GraphDataLoader, pad_spec_for
    from hydragnn_tpu.graph.batch import GraphSample, HeadSpec
    from hydragnn_tpu.graph.neighborlist import radius_graph
    from hydragnn_tpu.models.base import GraphHeadCfg, ModelConfig
    from hydragnn_tpu.models.create import create_model
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.trainer import create_train_state

    rng = np.random.RandomState(11)
    samples = []
    for _ in range(n_train + 16):
        pos = rng.rand(12, 3).astype(np.float32) * 2.0
        x = rng.rand(12, 1).astype(np.float32)
        ei = radius_graph(pos, 1.2, 12)
        samples.append(GraphSample(x=x, pos=pos, edge_index=ei,
                                   graph_y=x.sum(keepdims=True)[0],
                                   node_y=x))
    heads = [HeadSpec("e", "graph", 1)]
    pad = pad_spec_for(samples, batch_size)
    if stream:
        # identical samples land in a gpack store; the three phases train
        # through StreamingGraphLoaders with the same seed/shuffle, so any
        # parity break is the stream plan's fault, nothing else's
        from hydragnn_tpu.data.gpack import GpackDataset, GpackWriter
        from hydragnn_tpu.data.stream.loader import StreamingGraphLoader

        store_path = os.path.join(workdir, "stream_store.gpack")
        written = store_path + ".p0"  # GpackWriter's rank-0 suffix
        if not os.path.exists(written):
            GpackWriter(store_path).save(samples)
        store = GpackDataset(written)
        n = len(samples)
        mks = lambda lo, hi, shuffle: StreamingGraphLoader(  # noqa: E731
            store, np.arange(lo, hi), heads, batch_size,
            window=max(4, 2 * batch_size), shuffle=shuffle, seed=13,
            pad_specs=[pad])
        loaders = (mks(0, n_train, True),
                   mks(n_train, n_train + 8, False),
                   mks(n_train + 8, n, False))
    else:
        mk = lambda split, shuffle: GraphDataLoader(  # noqa: E731
            split, heads, batch_size, pad_spec=pad, shuffle=shuffle, seed=13)
        loaders = (mk(samples[:n_train], True),
                   mk(samples[n_train:n_train + 8], False),
                   mk(samples[n_train + 8:], False))
    cfg = ModelConfig(
        model_type="SAGE", input_dim=1, hidden_dim=8, output_dim=(1,),
        output_type=("graph",), graph_head=GraphHeadCfg(1, 8, 1, (8,)),
        node_head=None, task_weights=(1.0,), num_conv_layers=2)
    model = create_model(cfg)
    opt = select_optimizer({"type": "AdamW", "learning_rate": 0.01})
    state = create_train_state(model, next(iter(loaders[0])), opt)
    return model, cfg, opt, state, loaders


def run_child(args) -> int:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from hydragnn_tpu.resilience import load_resume_bundle, resume_dir
    from hydragnn_tpu.train.trainer import train_validate_test

    n_train = args.n_train or (
        8 * args.batch_size if args.mesh else 6 * args.batch_size)
    model, cfg, opt, state, loaders = _build(
        n_train, args.batch_size, args.epochs, args.mesh,
        stream=args.stream, workdir=args.workdir)
    logs_dir = os.path.join(args.workdir, "logs")
    log_name = "crashtest" if args.mode != "baseline" else "baseline"

    if args.mode == "reshard":
        return run_reshard_child(args, state, logs_dir)

    resume_meta = None
    if args.mode == "resume":
        bundle = load_resume_bundle(state,
                                    resume_dir(logs_dir, "crashtest"))
        if bundle is None:
            print("crashtest child: NO RESUME BUNDLE FOUND", flush=True)
            return 3
        state, resume_meta = bundle
        print(f"crashtest child: resuming from epoch "
              f"{resume_meta['epoch']} item "
              f"{resume_meta['items_consumed']}", flush=True)

    train_l, val_l, test_l = loaders
    if args.epoch_sleep > 0 and args.mode == "victim":
        # widen the mid-epoch window so the parent's SIGTERM lands there
        class SlowLoader:
            def __init__(self, loader, dt):
                self.loader, self.dt = loader, dt

            def set_epoch(self, e):
                self.loader.set_epoch(e)

            def __len__(self):
                return len(self.loader)

            def __iter__(self):
                for b in self.loader:
                    time.sleep(self.dt)
                    yield b

        train_l = SlowLoader(train_l, args.epoch_sleep)

    training = {"num_epoch": args.epochs}
    if args.zero:
        training["zero_stage"] = args.zero
    state, history = train_validate_test(
        model, cfg, state, opt, train_l, val_l, test_l,
        {"Training": training,
         "Variables_of_interest": {"output_names": ["e"]}},
        log_name=log_name, verbosity=1, logs_dir=logs_dir,
        use_mesh_dp=args.mesh, resume_meta=resume_meta)

    from hydragnn_tpu.resilience.ckpt_io import atomic_write_pickle

    final = os.path.join(args.workdir, f"{args.mode}_final.pk")
    atomic_write_pickle(final, jax.device_get(
        {"params": state.params, "opt_state": state.opt_state,
         "step": state.step,
         # per-epoch losses: the elastic verdict compares TRAJECTORIES
         # across device counts, where bit-identical params are not a
         # floating-point possibility
         "history": {"train": list(history["train"]),
                     "val": list(history["val"])}}))
    print(f"crashtest child: {args.mode} done "
          f"(preempted={bool(history.get('preempted'))}, "
          f"epochs={len(history['train'])})", flush=True)
    return 0


def run_reshard_child(args, skeleton, logs_dir) -> int:
    """Prove the elastic state contract at THIS process's device count:
    the victim's consolidated bundle, re-placed under the launched mesh at
    the launched ZeRO stage and consolidated again, is bit-for-bit the
    bundle — no leaf lost, no element changed, at a device count the
    bundle was never saved under."""
    import jax
    import numpy as np

    from hydragnn_tpu.parallel.mesh import make_mesh
    from hydragnn_tpu.parallel.zero import consolidate_state, reshard_state
    from hydragnn_tpu.resilience import load_resume_bundle, resume_dir

    bundle = load_resume_bundle(skeleton, resume_dir(logs_dir, "crashtest"))
    if bundle is None:
        print("crashtest child: NO RESUME BUNDLE FOUND", flush=True)
        return 3
    state, meta = bundle
    world = meta.get("world") or {}
    base = jax.device_get(state)
    mesh = make_mesh()
    st, zs = reshard_state(base, mesh, stage=args.zero)
    back = jax.device_get(
        consolidate_state(st, zs, mesh) if zs is not None else st)
    la = jax.tree_util.tree_leaves(base)
    lb = jax.tree_util.tree_leaves(back)
    bad = (len(la) != len(lb)
           or any(not np.array_equal(np.asarray(a), np.asarray(b))
                  for a, b in zip(la, lb)))
    n_dev = len(jax.devices())
    print(f"crashtest child: reshard round trip saved_dp="
          f"{world.get('dp_extent')} -> {n_dev} devices at zero_stage="
          f"{args.zero}: {'FAIL' if bad else 'OK'} "
          f"({len(la)} leaves)", flush=True)
    return 1 if bad else 0


# ---------------------------------------------------------------------------
# parent: orchestrate baseline -> victim (killed) -> resume -> compare
# ---------------------------------------------------------------------------


def _spawn(args, mode, extra_env=None, devices=None, batch_size=None,
           n_train=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    if devices is not None:
        # the elastic phases each relaunch at their OWN device count —
        # strip any inherited count so the override is authoritative
        flags = " ".join(
            f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f)
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{devices}").strip()
    elif args.mesh or args.zero:
        # the mesh/ZeRO paths need >1 device to mean anything: force a
        # virtual 4-device CPU mesh unless the caller (e.g. pytest's
        # conftest, 8 devices) already forced a count
        flags = env.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            env["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count=4").strip()
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           "--mode", mode, "--workdir", args.workdir,
           "--epochs", str(args.epochs),
           "--batch-size", str(batch_size or args.batch_size),
           "--epoch-sleep", str(args.epoch_sleep)]
    if n_train:
        cmd += ["--n-train", str(n_train)]
    if args.mesh:
        cmd.append("--mesh")
    if args.stream:
        cmd.append("--stream")
    if args.zero:
        cmd += ["--zero", str(args.zero)]
    return subprocess.Popen(cmd, cwd=REPO, env=env,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)


def _drain(proc, prefix, quiet_tail=0):
    """Stream child output; with quiet_tail > 0 print only the last N
    lines (the elastic matrix runs 20+ children) and return (rc, lines)."""
    lines = []
    for line in proc.stdout:
        lines.append(line.rstrip())
        if not quiet_tail:
            print(f"  [{prefix}] {line.rstrip()}")
    if quiet_tail:
        for line in lines[-quiet_tail:]:
            print(f"  [{prefix}] {line}")
        return proc.wait(), lines
    return proc.wait()


def _clean_workdir(workdir):
    import shutil

    for stale in ("logs", "baseline_final.pk", "victim_final.pk",
                  "resume_final.pk", "stream_store.gpack",
                  "stream_store.gpack.p0"):
        path = os.path.join(workdir, stale)
        if os.path.isdir(path):
            shutil.rmtree(path, ignore_errors=True)
        elif os.path.exists(path):
            os.unlink(path)


def run_elastic_parent(args) -> int:
    """The elastic matrix: victim at N=4 devices killed mid-epoch, resume
    at M = 3 and M = 5 with the global batch preserved (G = 60 samples
    per dispatch at every count, so each step covers the same sample
    set), across zero_stage 0/1/2 plus one streaming combo."""
    import numpy as np

    N, G = 4, 60
    n_train, epochs = 2 * G, args.epochs  # 2 dispatch units per epoch
    combos = [(stage, delta, False)
              for stage in (0, 1, 2) for delta in (-1, +1)]
    combos.append((0, -1, True))  # streaming loader rides the same path

    os.makedirs(args.workdir, exist_ok=True)
    print(f"crashtest: elastic matrix — victim N={N} devices, resume at "
          f"N-1/N+1, global batch {G} preserved, {len(combos)} combos")
    failures = []
    for stage, delta, stream in combos:
        M = N + delta
        args.zero, args.stream, args.mesh = stage, stream, True
        tag = (f"zero{stage} {N}->{M}" + (" stream" if stream else ""))
        _clean_workdir(args.workdir)
        print(f"crashtest: [{tag}] baseline — uninterrupted at {M} devices")
        rc, _ = _drain(_spawn(args, "baseline", devices=M,
                              batch_size=G // M, n_train=n_train),
                       "baseline", quiet_tail=1)
        if rc != 0:
            failures.append(f"{tag}: baseline rc={rc}")
            continue

        print(f"crashtest: [{tag}] victim at {N} devices, injected "
              "preemption at dispatch 1 (mid-epoch 0)")
        rc, _ = _drain(_spawn(args, "victim", devices=N,
                              batch_size=G // N, n_train=n_train,
                              extra_env={
                                  "HYDRAGNN_CHAOS_PREEMPT_STEP": "1"}),
                       "victim", quiet_tail=1)
        if rc != 0:
            failures.append(f"{tag}: victim rc={rc}")
            continue

        if stage == combos[0][0] and delta == combos[0][1] and not stream:
            # once: the DEFAULT policy must refuse the resize loudly
            print(f"crashtest: [{tag}] strict-policy probe — resume at "
                  f"{M} devices WITHOUT elastic_resume: epoch")
            rc, lines = _drain(_spawn(args, "resume", devices=M,
                                      batch_size=G // M, n_train=n_train),
                               "strict", quiet_tail=1)
            refused = rc != 0 and any("mismatch" in ln for ln in lines)
            if not refused:
                failures.append(f"{tag}: strict policy did NOT refuse "
                                f"(rc={rc})")
                continue
            print(f"  [parent] strict refusal confirmed (rc={rc})")

        print(f"crashtest: [{tag}] reshard round trip at {M} devices")
        rc, _ = _drain(_spawn(args, "reshard", devices=M,
                              batch_size=G // M, n_train=n_train),
                       "reshard", quiet_tail=1)
        if rc != 0:
            failures.append(f"{tag}: reshard round trip rc={rc}")
            continue

        print(f"crashtest: [{tag}] elastic resume at {M} devices "
              "(elastic_resume: epoch)")
        rc, _ = _drain(_spawn(args, "resume", devices=M,
                              batch_size=G // M, n_train=n_train,
                              extra_env={
                                  "HYDRAGNN_ELASTIC_RESUME": "epoch"}),
                       "resume", quiet_tail=2)
        if rc != 0:
            failures.append(f"{tag}: elastic resume rc={rc}")
            continue

        with open(os.path.join(args.workdir, "baseline_final.pk"),
                  "rb") as f:
            base = pickle.load(f)
        with open(os.path.join(args.workdir, "resume_final.pk"),
                  "rb") as f:
            res = pickle.load(f)
        bh, rh = base["history"], res["history"]
        # val: every epoch (end-of-epoch params at the same data
        # position); train: full epochs only — the resumed epoch 0
        # averages just the post-kill units, the baseline's all of them
        dv = -1.0
        val_ok = train_ok = len(rh["val"]) == len(bh["val"])
        if val_ok:
            dv = float(np.max(np.abs(
                np.subtract(rh["val"], bh["val"])
                / np.asarray(bh["val"]))))
            val_ok = np.allclose(rh["val"], bh["val"],
                                 rtol=args.elastic_rtol)
            train_ok = np.allclose(rh["train"][1:], bh["train"][1:],
                                   rtol=args.elastic_rtol)
        verdict = "PASS" if (val_ok and train_ok) else "FAIL"
        print(f"crashtest: [{tag}] PARITY {verdict} — val/train loss "
              f"trajectories vs fixed-{M}-device run (max rel dev "
              f"{dv:.2e}, tol {args.elastic_rtol:.0e})")
        if verdict == "FAIL":
            failures.append(
                f"{tag}: trajectory mismatch val={rh['val']} "
                f"baseline={bh['val']}")

    if failures:
        print(f"crashtest: ELASTIC PARITY FAIL — {len(failures)} of "
              f"{len(combos)} combos:")
        for f_ in failures:
            print(f"  - {f_}")
        return 1
    print(f"crashtest: ELASTIC PARITY PASS — all {len(combos)} combos "
          f"(reshard bit-exact, strict refusal, trajectory parity)")
    return 0


def run_parent(args) -> int:
    os.makedirs(args.workdir, exist_ok=True)
    # the workdir is reused across invocations (and across --mesh/--zero
    # flag combinations that change the steps-per-epoch numbering): stale
    # orbax checkpoints at a HIGHER step make the victim's bundle save a
    # silent no-op (orbax declines steps <= latest), so every run starts
    # from a clean scratch tree
    _clean_workdir(args.workdir)
    print(f"crashtest: workdir {args.workdir}")

    print("crashtest: phase 1/3 — uninterrupted baseline")
    rc = _drain(_spawn(args, "baseline"), "baseline")
    if rc != 0:
        print(f"crashtest: baseline FAILED rc={rc}")
        return rc

    if args.chaos_step:
        print(f"crashtest: phase 2/3 — victim with injected preemption at "
              f"dispatch {args.chaos_step}")
        victim = _spawn(args, "victim", extra_env={
            "HYDRAGNN_CHAOS_PREEMPT_STEP": str(args.chaos_step)})
        rc = _drain(victim, "victim")
    else:
        print("crashtest: phase 2/3 — victim, SIGTERM "
              f"{args.kill_delay:.1f}s after its first epoch line")
        victim = _spawn(args, "victim")
        killed = False
        for line in victim.stdout:
            print(f"  [victim] {line.rstrip()}")
            if not killed and line.lstrip().startswith("Epoch:"):
                time.sleep(args.kill_delay)
                victim.send_signal(signal.SIGTERM)
                killed = True
                print("  [parent] SIGTERM sent")
        rc = victim.wait()
        if not killed:
            print("crashtest: victim finished before the kill — raise "
                  "--epochs or --epoch-sleep")
            return 4
    if rc != 0:
        print(f"crashtest: victim FAILED rc={rc} (expected graceful exit)")
        return rc

    bundle_meta = os.path.join(args.workdir, "logs", "crashtest", "resume",
                               "resume_meta.json")
    if not os.path.exists(bundle_meta):
        print("crashtest: FAIL — victim exited without a resume bundle")
        return 5

    print("crashtest: phase 3/3 — resume from the bundle")
    rc = _drain(_spawn(args, "resume"), "resume")
    if rc != 0:
        print(f"crashtest: resume FAILED rc={rc}")
        return rc

    import numpy as np

    with open(os.path.join(args.workdir, "baseline_final.pk"), "rb") as f:
        base = pickle.load(f)
    with open(os.path.join(args.workdir, "resume_final.pk"), "rb") as f:
        res = pickle.load(f)

    import jax

    lb = jax.tree_util.tree_leaves(base["params"])
    lr_ = jax.tree_util.tree_leaves(res["params"])
    mismatch = [i for i, (a, b) in enumerate(zip(lb, lr_))
                if not np.array_equal(np.asarray(a), np.asarray(b))]
    # under --zero the dumped states are CONSOLIDATED — comparing the
    # optimizer moments too proves the consolidate/re-shard round trip
    # preserved them bit-for-bit, not just the params
    ob = jax.tree_util.tree_leaves(base["opt_state"])
    or_ = jax.tree_util.tree_leaves(res["opt_state"])
    opt_mismatch = [i for i, (a, b) in enumerate(zip(ob, or_))
                    if not np.array_equal(np.asarray(a), np.asarray(b))]
    steps = (int(base["step"]), int(res["step"]))
    tag = f" (zero_stage={args.zero})" if args.zero else ""
    # zip truncates: unequal leaf COUNTS (a consolidate/re-shard that drops
    # or fails to restore trailing leaves) must fail, not pass on the prefix
    if len(lb) != len(lr_) or len(ob) != len(or_):
        print(f"crashtest: PARITY FAIL{tag} — leaf count mismatch "
              f"(params {len(lb)} vs {len(lr_)}, opt {len(ob)} vs "
              f"{len(or_)})")
        return 1
    if not mismatch and not opt_mismatch and steps[0] == steps[1]:
        print(f"crashtest: PARITY PASS{tag} — {len(lb)} param + {len(ob)} "
              f"opt-state leaves identical, step {steps[0]} == {steps[1]}")
        return 0
    print(f"crashtest: PARITY FAIL{tag} — {len(mismatch)}/{len(lb)} param "
          f"and {len(opt_mismatch)}/{len(ob)} opt-state leaves differ, "
          f"steps {steps[0]} vs {steps[1]}")
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workdir", default="/tmp/hydragnn_crashtest")
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--kill-delay", type=float, default=1.0)
    ap.add_argument("--epoch-sleep", type=float, default=0.3,
                    help="victim-only per-batch sleep widening the "
                         "mid-epoch kill window")
    ap.add_argument("--chaos-step", type=int, default=0,
                    help="use injected preemption at this dispatch instead "
                         "of a real SIGTERM (fully deterministic)")
    ap.add_argument("--mesh", action="store_true",
                    help="exercise the mesh-DP path")
    ap.add_argument("--stream", action="store_true",
                    help="train all three phases through the streaming "
                         "data plane (gpack store + windowed loaders); the "
                         "resume phase fast-forwards inside the stream plan")
    ap.add_argument("--zero", type=int, nargs="?", const=1, default=0,
                    choices=(0, 1, 2),
                    help="ZeRO stage for all three phases (implies --mesh): "
                         "proves consolidate-on-save / re-shard-on-resume "
                         "preserves mid-epoch bit parity")
    ap.add_argument("--elastic", action="store_true",
                    help="run the elastic resize matrix: victim killed at "
                         "4 devices, resumed at 3 and 5 across zero_stage "
                         "0/1/2 + streaming (see module docstring)")
    ap.add_argument("--elastic-rtol", type=float, default=2e-2,
                    help="loss-trajectory tolerance for the elastic "
                         "verdict (cross-device-count FP regroup)")
    ap.add_argument("--n-train", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--mode",
                    choices=("baseline", "victim", "resume", "reshard"),
                    default="baseline", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.zero:
        args.mesh = True
    if args.child:
        return run_child(args)
    if args.elastic:
        return run_elastic_parent(args)
    return run_parent(args)


if __name__ == "__main__":
    sys.path.insert(0, REPO)
    sys.exit(main())
