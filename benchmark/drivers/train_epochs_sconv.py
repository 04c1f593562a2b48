"""train_epochs_sconv — the language-model epoch driver for the
short-convolution stack (``model_type: "Lfm2Moe"``).

Everything that times the job is the stock driver's and everything that
sums the step records the language-model driver's (``train_epochs.py`` and
``train_epochs_lm.py``, loaded by path and left untouched: ``_run``, the
region clock, the timed loader, the profiler window, the median epoch
rate, checks (b)-(g)).  What differs is this file:

* ``run`` copies the configuration's top-level keys (the public
  ``config.json``'s names) to ``Architecture.lfm2_moe``, where this stack
  reads them.
* ``facts["lm"]`` is ``sconv_counts.lm_facts`` (the convolution's held
  shapes), and each counted epoch gains the step records' ``sconv`` block
  (``rows``, ``starts``, ``taps_cut``) and the bias's counters.
* ``correct`` (a) is ``reference_parity`` below, the comparison
  ``train_epochs_mla.py`` makes: after the window, the forward and backward
  pass of the TIMED program (``trainer._loss_and_metrics`` in train mode;
  the optimizer is left out) on the cell's first micro-batch, padded to the
  dispatch group's shape, against the plain reference
  (``reference/lfm2_moe_reference.py``: float32, "highest", one document at
  a time with no boundary logic at all, attention in query blocks) on the
  same seeded weights and a seeded non-zero expert bias.  Compared: the
  loss, the global gradient norm, and per parameter group (the tied table,
  per layer ``W_in``, the taps, ``W_out``, attention, the qk norms, the
  dense feed-forward, router, experts) the norm of the gradient and the
  norm of the DIFFERENCE over the reference's norm.  Two rungs: the program
  forced to float32 under "highest" (summation order only), and as shipped
  (bfloat16 products).  And, of the same train-mode pass: ``b`` stepped by
  exactly its update speed on every expert layer.
* two more checks on the step records: every counted step reported the
  slots on all 64 experts and ``|b|`` grew; and every counted dispatch's
  ``sconv.starts`` equals its real graphs times the conv layers (a tap was
  cut once a document and a layer, never inside one).

As there, nothing compiled here closes over a seeded value, the reference
compiles each KIND of layer once, and the reference, the two traces and the
two compiles overlap, all AFTER the window: ``setup_s`` and the ``setup_*``
readers see the trainer's builds alone.  One thing more than there: what
the comparison builds (its two programs, the reference's pieces) is
compiled at ``exec_time_optimization_effort`` -1.  The operations and their
dtypes are what was traced, as before; the compiler searches less for a
fast schedule, which these programs, run once, do not need: compiled for a
described v5e the whole forward and backward pass takes 0.6x the time to
build and 0.6x the code, and with it a cold run of this cell ends inside
the check's 360 s (PERF.md section 6, PR 40).
"""

from __future__ import annotations

import concurrent.futures
import copy
import importlib.util
import os
import sys
import time

_HERE = os.path.dirname(os.path.abspath(__file__))
_BENCH = os.path.dirname(_HERE)


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# an instance of the language-model driver of our own (it loads its own
# instance of the stock driver): its ``_run`` finds the names it looks up
# in its module at call time replaced by this stack's
_lm = _load("benchmark_sconv_lm_train_epochs",
            os.path.join(_HERE, "train_epochs_lm.py"))
_stock = _lm._stock
_counts = _load("benchmark_sconv_counts",
                os.path.join(_BENCH, "sconv_counts.py"))
_reference = _load("benchmark_sconv_reference", os.path.join(
    _BENCH, "reference", "lfm2_moe_reference.py"))

# Limits of the comparison, per rung: on the loss, on the whole gradient's
# difference from the reference's (norm of the difference over the
# reference's norm) and on each parameter group's (its norm, its
# difference), the ROUTED groups (router, experts) apart from the others.
# Each lies between two readings on the v5e (PERF.md section 4, PR 40:
# twenty-two seeds, a float8 probe on three of them).
#
# Program forced to float32 under "highest" vs the reference: both are true
# float32 and differ by summation order (the fused taps against the padded
# document, blocked softmax, grouped products over sorted rows, the sliced
# feed-forward) and transcendental rounding: in eighteen of twenty-two seeds
# the whole gradient reads 2.2-3.7e-6, the loss <= 1.6e-7, the worst group
# 1.3-2.5e-5 (the qk norms), every other <= 5.5e-6.  In the other four
# some node's 4th and 5th expert tie to float32 rounding (4 of 64 under a
# bias, four expert layers, ~19 k nodes), and a swap is a different function
# with NO shared expert beside it to dilute it: where neither is held it
# moves that layer's router group alone (6.9e-3, 8.2e-3 and 2.0e-2; the
# whole gradient 3.8e-5 to 1.2e-4), and once a HELD expert was swapped in the
# last layer (its experts 7.7e-3, its router 2.1e-3, every other group
# 2-9.6e-4, the whole gradient 5.6e-4, the loss 8e-8).  The limits leave
# room for a few such swaps and stay under what bfloat16 products give:
# whole gradient 2.5e-3 (bfloat16: 2.2-4.4e-2), routed groups 5e-2
# (bfloat16: every one >= 1.2e-1), the other groups 5e-3 (bfloat16: every
# one >= 1.2e-2).  ISSUE 40 set 2e-4 / 2e-4 / 1e-3 and expected a tie to be
# rare, as on GLM's cell (none in eleven seeds at the same 4 of 64).
TOL_F32 = {"loss": 2e-4, "grad": 2.5e-3, "group": 5e-3, "routed": 5e-2}
# As shipped (bfloat16 operands, float32 accumulation; B, C, X and y of the
# short convolution rounded to bfloat16) vs the reference, and the
# reference with every product's operands rounded to float8_e4m3fn
# (LFM2_PROBE_PRODUCTS) vs itself, three seeds, two of them at the
# committed 23,512 rows:
#   loss              6.3e-7 - 8.2e-5 as shipped    1.4e-4 - 4.0e-4 in float8
#   whole gradient    2.2e-2 - 4.4e-2               9.7e-1 - 9.8e-1
#   routed groups     <= 3.3e-1 (a late router)     1.0 - 1.1 (every one)
#   the other groups  1.2e-2 - 7.2e-2               0.91 - 1.3
# The loss carries no limit on this rung, as on GLM's and Nemotron's (float8
# moves it by 1.4-4.0e-4, bfloat16 by up to 8e-5: too near; the float32
# rung holds the loss).  Everything reads four times GLM's cell (7-10e-3 on the
# whole gradient there, routed groups 4-9e-2): rounding the residual stream
# swaps the last selected expert of a few percent of the nodes, as there,
# but here a node's feed-forward half IS its held experts, often a single
# one, and a swap takes that half from something to nothing; every group
# upstream sees it (one swap alone reads 2-9.6e-4 on every group, above).
# The router groups grow with depth (1.6e-1 in layer 1 to 3.3e-1 in layer
# 4), the others from 3.0e-2 to 6.8e-2; attention's group, whose forward
# input no expert has touched yet, reads 1.3-1.7e-2.  Counted on the chip
# (seed 4000400505): 3.1 / 5.5 / 8.0 / 10.5 % of 19,103 nodes select other
# experts as shipped than in float32 in expert layers 1-4, and with the
# selection pinned to float32's the routers read 1.7-2.8e-2 and the whole
# gradient 1.2e-2 where they read 1.2-2.9e-1 and 3.0e-2 unpinned.
TOL_SHIPPED = {"loss": None, "grad": 1e-1, "group": 2e-1, "routed": 6e-1}
ROUTED = ("router", "experts")
Q_BLOCK = 1024          # the reference's attention, rows at a time
BIAS_SCALE = 0.02       # the seeded bias of the comparison: about what
#                         30 train steps of 0.001 reach
BIAS_UPDATE_SPEED = 1e-3        # models/glm_moe_lite.py, ASSUMED
_GROUPS = {"w_in": "w_in", "conv_w": "conv", "w_out": "w_out",
           "wq": "attn", "wk": "attn", "wv": "attn", "wo": "attn",
           "q_norm": "qk_norm", "k_norm": "qk_norm", "w1": "ffn",
           "w3": "ffn", "w2": "ffn", "router": "router",
           "experts_w1": "experts", "experts_w3": "experts",
           "experts_w2": "experts"}


def group_of(path: str, kinds=()) -> str:
    """A parameter's group for the comparison, from its tree path
    (``layer_3/op/w_in`` -> ``layer_3.w_in``); a half-layer's input norm
    goes with the first matrix that reads it (``kinds``: the held
    ``layer_types``, which say whether ``layer_2/op/norm`` feeds ``w_in``
    or ``attn``; ``layer_3/moe/norm`` goes with ``router``); the output
    norm with the table it feeds."""
    parts = path.split("/")
    if not parts[0].startswith("layer_"):
        return "table"
    group = _GROUPS.get(parts[-1]) or {
        "ffn": "ffn", "moe": "router",
        "op": "w_in" if kinds[int(parts[0][len("layer_"):])] == "conv"
        else "attn"}[parts[1]]
    return f"{parts[0]}.{group}"


def reference_parity(job, say):
    """``_compare`` with the compiler told to search less: what is built
    from here on is run once (the module's docstring).  The setting is the
    process's, so the threads' builds have it too; the job is over."""
    import jax

    effort = jax.config.jax_exec_time_optimization_effort
    jax.config.update("jax_exec_time_optimization_effort", -1.0)
    try:
        return _compare(job, say)
    finally:
        jax.config.update("jax_exec_time_optimization_effort", effort)


def _compare(job, say):
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
    # the epoch's steps are ONE dispatch group: every step is padded to
    # the group's fitted shape, so that is the timed shape
    nodes = max(b.num_nodes for b in loader)
    spec = next(p for p in loader.pad_specs if p.num_nodes == nodes)
    batch = jax.device_put(collate(samples, spec, job["head_specs"],
                                   *job["slices"]))
    say(f"parity: the first {len(samples)} train documents, "
        f"{sum(s.num_nodes for s in samples)} tokens, in the dispatch "
        f"group's shape of {spec.num_nodes} nodes")
    docs = [np.asarray(s.x[:, 0], np.int32) for s in samples]
    arch = job["config"]["NeuralNetwork"]["Architecture"]
    lm, share = arch["lfm2_moe"], arch["share"]
    kinds = lm["layer_types"]

    t_start = time.monotonic()

    def paths(tree):
        return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
                for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}

    def on_host(tree):
        """Leaves by path as numpy, the device's copy dropped: the
        reference's gradients wait on the host while the program's are
        made."""
        out = {path: np.asarray(leaf) for path, leaf in paths(tree).items()}
        jax.tree.map(lambda a: a.delete(), tree)
        return out

    @jax.jit
    def sums(g, r):
        g = g.astype(jnp.float32)
        return jnp.stack([jnp.sum(jnp.square(g)), jnp.sum(jnp.square(r)),
                          jnp.sum(jnp.square(g - r))])

    def compare(got, ref):
        """Per group the norms of ``got`` (on the device), of ``ref`` (on
        the host, sent up a leaf at a time) and of their difference."""
        sq = {}
        for path, g in paths(got).items():
            acc = sq.setdefault(group_of(path, kinds), np.zeros(3))
            acc += np.asarray(sums(g, ref[path]), np.float64)
        return {k: tuple(float(x) for x in np.sqrt(v))
                for k, v in sq.items()}

    # the weights the trainer started from: the same seed, the same init.
    # Whatever the seed draws (keys, token ids, the bias) is an ARGUMENT of
    # every function compiled here: closed over, it would be a constant of
    # the program, and every seed would compile its own
    variables = jax.jit(lambda key, drop, b: job["model"].init(
        {"params": key, "dropout": drop}, b, train=False))(
            jax.random.PRNGKey(job["seed"]),
            jax.random.PRNGKey(job["seed"] + 1), batch)
    params = variables["params"]
    stats = dict(variables["batch_stats"])
    names = sorted((k[len("bias_"):] for k in stats if k.startswith("bias_")),
                   key=lambda n: int(n[len("layer_"):]))
    keys = jax.random.split(jax.random.PRNGKey(job["seed"] + 2), len(names))
    for name, key in zip(names, keys):
        stats[f"bias_{name}"] = BIAS_SCALE * jax.random.normal(
            key, stats[f"bias_{name}"].shape, jnp.float32)
    biases = {name: stats[f"bias_{name}"] for name in names}

    def lowered(cfg, precision):
        """The timed program's forward and backward pass, traced here; it
        compiles on a thread of its own while the reference runs."""
        model = create_model(cfg)

        def loss_fn(p, stats, batch):   # the logits stay inside
            loss, (_heads, new_stats, _out) = _loss_and_metrics(
                model, cfg, p, stats, batch, True)
            return loss, new_stats

        with (jax.default_matmul_precision(precision) if precision
              else contextlib.nullcontext()):
            return jax.jit(jax.value_and_grad(
                loss_fn, has_aux=True)).lower(params, stats, batch)

    def program(label, compiled, tol):
        t0 = time.monotonic()
        (loss, new_stats), grads = compiled.result()(params, stats, batch)
        # of the same train-mode pass: the bias's step on each layer
        moved = {n: float(jnp.max(jnp.abs(new_stats[f"bias_{n}"] - biases[n])))
                 for n in names}
        out = rung(label, float(loss), grads, tol)
        jax.tree.map(lambda a: a.delete(), grads)
        say(f"parity: program {label} run and compared in "
            f"{time.monotonic() - t0:.1f}s")
        return out, moved

    def reference(label):
        t0 = time.monotonic()
        # every document padded (masked) to one length, the longest's
        # rounded up to the reference's row block: one shape to compile
        longest = -(-max(len(d) for d in docs) // Q_BLOCK) * Q_BLOCK
        loss, grads = _reference.loss_and_grads(
            params, lm, share, biases, docs, q_block=Q_BLOCK,
            pad_to=lambda n: longest)
        grads = on_host(grads)
        say(f"parity: {label}, {len(docs)} documents one at a time, each "
            f"padded to {longest} tokens, in {time.monotonic() - t0:.1f}s")
        return loss, grads

    with concurrent.futures.ThreadPoolExecutor(3) as pool:
        # the reference first, on a thread of its own: its pieces compile
        # and run while this thread traces the two programs, each of which
        # then compiles on a thread too
        ref = pool.submit(reference, "reference")
        compiled = [pool.submit(lowered(cfg, precision).compile)
                    for cfg, precision in (
                        (job["cfg"], None),
                        (dataclasses.replace(job["cfg"],
                                             compute_dtype="float32"),
                         "highest"))]
        say(f"parity: weights made and both programs traced in "
            f"{time.monotonic() - t_start:.1f}s")
        for c in compiled:
            c.result()
        say(f"parity: both programs compiled "
            f"{time.monotonic() - t_start:.1f}s in")
        ref_loss, ref_grads = ref.result()

    def rung(label, loss, grads, tol):
        groups = compare(grads, ref_grads)
        g_all, r_all, d_all = (
            float(np.sqrt(sum(v[i] ** 2 for v in groups.values())))
            for i in range(3))
        # (deviation, its limit or None) per compared number
        devs = {"loss": (_stock._rel(loss, ref_loss), tol["loss"]),
                "grad_norm": (_stock._rel(g_all, r_all), tol["grad"]),
                "grad_diff": (d_all / max(r_all, 1e-30), tol["grad"])}
        for k, (g, r, d) in groups.items():
            limit = tol["routed" if k.endswith(ROUTED) else "group"]
            devs[f"{k}.norm"] = (_stock._rel(g, r), limit)
            devs[f"{k}.diff"] = (d / max(r, 1e-30), limit)
        held = {k: v for k, v in devs.items() if v[1] is not None}
        worst = max(held, key=lambda k: held[k][0] / held[k][1])
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

    shipped, moved_shipped = program("as_shipped", compiled[0], TOL_SHIPPED)
    highest, moved_highest = program("highest", compiled[1], TOL_F32)
    out = {"as_shipped": shipped, "highest": highest}
    # exactly one step of the update speed, up or down, on every expert
    # layer (an expert whose load IS the mean stays: not every entry moves)
    out["bias_step"] = moved_shipped
    want = len(kinds) - int(lm["num_dense_layers"])
    bias_ok = len(names) == want and all(
        abs(m - BIAS_UPDATE_SPEED) <= 1e-6
        for moved in (moved_shipped, moved_highest) for m in moved.values())
    say(f"parity: the bias's step by layer {out['bias_step']} "
        f"(want {BIAS_UPDATE_SPEED:g} on each of {want})")
    probe = os.environ.get("LFM2_PROBE_PRODUCTS")
    if probe:
        # the builder's reading of "the nearest precision below": the
        # reference with every product's operands rounded to ``probe``
        # against the same reference gradients; refuses nothing
        _reference.PRODUCT_DTYPE = jnp.dtype(probe)
        try:
            low_loss, low_grads = reference(
                f"reference with {probe} products")
        finally:
            _reference.PRODUCT_DTYPE = None
        rung(f"reference_in_{probe}", low_loss,
             {k: jnp.asarray(v) for k, v in low_grads.items()}, TOL_SHIPPED)
    out["ok"] = bias_ok and all(out[k]["dev"] <= out[k]["tol"]
                                for k in ("highest", "as_shipped"))
    return out


_lm.reference_parity = reference_parity
_lm._counts = _counts


def run(ctx):
    import numpy as np

    config = copy.deepcopy(ctx["config"])
    arch = config["NeuralNetwork"]["Architecture"]
    arch["lfm2_moe"] = {k: v for k, v in config.items()
                        if k not in _lm._HF_SKIP}
    arch["share"] = config["share"]
    config["corpus"]["params"]["vocab_size"] = config["vocab_size"]
    _stock._run = _lm._run
    result = _stock.run({**ctx, "config": config})

    # the step records once more, for what only this stack reports
    by_epoch = {}
    for ev in _stock._read_events(os.path.join(ctx["workdir"], "logs")):
        if ev.get("event") == "step":
            by_epoch.setdefault(ev["epoch"], []).append(ev)
    conv_layers = sum(k == "conv" for k in config["layer_types"])
    epochs = result["facts"]["epochs"]
    starts_ok = bool(epochs)
    for e in epochs:
        recs = by_epoch.get(e["epoch"], [])
        moe = [r["moe"] for r in recs if "moe" in r]
        for key in ("load_all_max_over_mean", "bias_abs_max"):
            vals = [m[key] for m in moe if key in m]
            e[f"moe_{key}"] = float(np.mean(vals)) if vals else None
        conv = [r["sconv"] for r in recs if "sconv" in r]
        for key in ("rows", "starts", "taps_cut"):
            e[f"sconv_{key}"] = (float(sum(c[key] for c in conv))
                                 if conv else None)
        # a graph starts once a layer, in every counted dispatch
        starts_ok = starts_ok and len(conv) == len(recs) > 0 and all(
            r["sconv"]["starts"] == conv_layers * r["num_graphs"]
            for r in recs)
    grew = [e["moe_bias_abs_max"] for e in epochs]
    ok = bool(epochs) and all(
        e["moe_load_all_max_over_mean"] is not None for e in epochs) and (
            grew[0] is not None and grew[0] > 0 and grew[-1] >= grew[0])
    ctx["say"](f"bias: |b| max by counted epoch "
               f"{[round(g, 4) if g is not None else None for g in grew]}; "
               f"all-expert load max/mean "
               f"{[e['moe_load_all_max_over_mean'] for e in epochs[:3]]}")
    ctx["say"](f"sconv: rows / starts / taps cut by counted epoch "
               f"{[(e['sconv_rows'], e['sconv_starts'], e['sconv_taps_cut']) for e in epochs[:3]]}"
               f"; graphs {[e['graphs'] for e in epochs[:3]]} x "
               f"{conv_layers} conv layers")
    if not ok:
        ctx["say"]("CHECK FAILED bias: the step records carry no "
                   "full-width counts, or |b| did not grow")
    if not starts_ok:
        ctx["say"]("CHECK FAILED sconv: a counted dispatch has no sconv "
                   "block, or its starts are not its real graphs times the "
                   "conv layers")
    result["correct"] = bool(result["correct"] and ok and starts_ok)
    return result
