"""train_epochs_ssm — the language-model epoch driver for the state-space
stack (``model_type: "NemotronH"``).

Everything that times the job is the stock driver's and everything that
sums the step records the language-model driver's (``train_epochs.py`` and
``train_epochs_lm.py``, loaded by path and left untouched: ``_run``, the
region clock, the timed loader, the profiler window, the median epoch
rate, checks (b)-(g)).  What differs is this file:

* ``run`` copies the configuration's top-level keys (the public
  ``config.json``'s names) to ``Architecture.nemotron_h``, where this stack
  reads them.
* ``facts["lm"]`` is ``ssm_counts.lm_facts`` (the scan's held shapes), and
  each counted epoch gains the step records' ``ssm`` block (``chunks``,
  ``chunks_padding``, ``resets``) and the bias's counters.
* ``correct`` (a) is ``reference_parity`` below, the comparison
  ``train_epochs_mla.py`` makes: after the window, the forward and backward
  pass of the TIMED program (``trainer._loss_and_metrics`` in train mode;
  the optimizer is left out) on the cell's first micro-batch, padded to the
  dispatch group's shape, against the plain reference
  (``reference/nemotron_h_reference.py``: float32, "highest", one document
  at a time, the recurrence one token a step, attention in query blocks)
  on the same seeded weights and a seeded non-zero correction bias.  The
  program scans its five (E, M) pairs over stacked parameters; the
  comparison is by LAYER: stacked leaves are sliced, and each layer's
  gradient is held to the reference's.  Compared: the loss, the global
  gradient norm, and per parameter group (embedding, head, per layer
  ``W_in``, conv, ``A_log``/``D``/``dt_bias``, ``W_out``, attention,
  router, latent projections, experts, shared) the norm of the gradient
  and the norm of the DIFFERENCE over the reference's norm.  Two rungs: the
  program forced to float32 under "highest" (summation order only), and as
  shipped (bfloat16 products).  And, of the same train-mode pass: ``b``
  stepped by exactly its update speed on every expert layer.
* two more checks on the step records: every counted step reported the
  slots on all 512 experts and ``|b|`` grew; and every counted dispatch's
  ``ssm.resets`` equals its real graphs (a state was started once a
  document, never inside one).

As there, nothing compiled here closes over a seeded value, the reference
compiles each KIND of layer once, and the reference, the two traces and
the two compiles overlap.  The reference's parameters are one entry a
layer; they are made first, the reference runs and its gradients go to the
host, and only then are the program's stacked parameters made (from the
same seed): the two trees never wait on the device together.
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
_lm = _load("benchmark_ssm_lm_train_epochs",
            os.path.join(_HERE, "train_epochs_lm.py"))
_stock = _lm._stock
_counts = _load("benchmark_ssm_counts", os.path.join(_BENCH, "ssm_counts.py"))
_reference = _load("benchmark_ssm_reference", os.path.join(
    _BENCH, "reference", "nemotron_h_reference.py"))

# Limits of the comparison, per rung: on the loss, on the whole gradient's
# difference from the reference's (norm of the difference over the
# reference's norm) and on each parameter group's (its norm, its
# difference), the ROUTED groups (router, latent projections, experts)
# apart from the others.  Each lies between two readings on the v5e
# (PERF.md section 4, PR 37: eleven seeds and two float8 probes).
#
# Program forced to float32 under "highest" vs the reference: both are true
# float32 and differ by summation order (the chunked scan against the
# token-by-token recurrence, blocked softmax, grouped products over sorted
# rows) and transcendental rounding: in ten of eleven seeds the whole
# gradient reads 1.0-1.9e-5, the loss <= 2.5e-7, every group but the routed
# ones <= 1.5e-4 and the routed ones 1.2e-5 to 1.3e-3.  With 22 of 512
# experts a node, five expert layers and ~6 k nodes, some node's 22nd and
# 23rd expert tie to float32 rounding in most seeds, and a swapped expert is
# a different function: where neither is held it moves rows of the layer's
# router group alone (up to 1.3e-3), and in ONE seed of eleven a HELD expert
# was swapped in layer 2: that layer's routed groups read 1.0e-2, every
# other group 0.9-1.3e-3, the whole gradient 6.9e-4, the loss 1.4e-6
# (Laguna's driver met the first kind once in ten seeds at 10 of 256).  The
# limits leave room for two such swaps and stay under what bfloat16
# products give: whole gradient 2.5e-3 (bfloat16: 8.0-8.8e-3), routed
# groups 3e-2 (bfloat16: the largest 7.3e-2 to 1.2e-1), the other groups
# 5e-3 (bfloat16: the largest 1.3-2.2e-2).
TOL_F32 = {"loss": 2e-4, "grad": 2.5e-3, "group": 5e-3, "routed": 3e-2}
# As shipped (bfloat16 operands, float32 accumulation) vs the reference,
# and the reference with every product's operands rounded to float8_e4m3fn
# (NEMOTRON_PROBE_PRODUCTS) vs itself:
#   loss              1.1e-5 - 8.2e-5 as shipped    2.1e-4, 2.3e-4 in float8
#   whole gradient    8.0e-3 - 8.8e-3               8.7e-1, 8.8e-1
#   routed groups     7.3e-2 - 1.2e-1 at most       1.0 (every one >= 0.98)
#   the other groups  1.3e-2 - 2.2e-2 at most       0.9 - 2.4 (the head 8.9e-2)
# The loss carries no limit on this rung, as on GLM's (float8 moves it by
# 2e-4, bfloat16 by up to 8e-5: too near; the float32 rung holds the
# loss).  The routed groups read five times the others: rounding the
# residual stream swaps some nodes' last selected experts, more of them in
# the later layers (layer 0's read 2e-2, layer 6's and 8's 7e-2 to 1.2e-1).
# The issue expected less than GLM's 2e-1 "since a swapped expert is 1/22
# of a node's routed output"; it reads about the same as GLM's 4e-2 to
# 9e-2, because 22 of 512 has that many more near-ties to swap.
TOL_SHIPPED = {"loss": None, "grad": 2.5e-2, "group": 1e-1, "routed": 3e-1}
ROUTED = ("router", "latent", "experts")
Q_BLOCK = 1024          # the reference's attention, rows at a time
BIAS_SCALE = 0.02       # the seeded bias of the comparison: about what
#                         30 train steps of 0.001 reach
BIAS_UPDATE_SPEED = 1e-3        # models/glm_moe_lite.py, ASSUMED
_GROUPS = {"in_proj": "w_in", "norm": None, "conv_w": "conv",
           "conv_b": "conv", "A_log": "ssm", "D": "ssm", "dt_bias": "ssm",
           "gate_norm": "w_out", "out_proj": "w_out", "router": "router",
           "down": "latent", "up": "latent", "experts_w1": "experts",
           "experts_w2": "experts", "shared_w1": "shared",
           "shared_w2": "shared", "wq": "attn", "wk": "attn", "wv": "attn",
           "wo": "attn"}


def group_of(path: str, kind: str = "") -> str:
    """A parameter's group for the comparison, from its path in the
    reference's tree (``layer_3/in_proj`` -> ``layer_3.w_in``); a layer's
    input norm goes with the first matrix that reads it (``kind``: the
    layer's letter in the pattern)."""
    parts = path.split("/")
    if not parts[0].startswith("layer_"):
        return "embed" if parts[0] == "embed" else "head"
    group = _GROUPS[parts[-1]] or {"M": "w_in", "E": "router",
                                   "*": "attn"}[kind]
    return f"{parts[0]}.{group}"


def reference_parity(job, say):
    import contextlib
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from hydragnn_tpu.graph.batch import collate
    from hydragnn_tpu.models.create import create_model
    from hydragnn_tpu.models.nemotron_h import layer_trees
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
    lm, share = arch["nemotron_h"], arch["share"]
    pattern = lm["hybrid_override_pattern"]

    t_start = time.monotonic()

    def paths(tree):
        return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
                for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}

    def on_host(tree):
        """Leaves by path as numpy, the device's copy dropped."""
        out = {path: np.asarray(leaf) for path, leaf in paths(tree).items()}
        jax.tree.map(lambda a: a.delete(), tree)
        return out

    @jax.jit
    def sums(g, r):
        g = g.astype(jnp.float32)
        return jnp.stack([jnp.sum(jnp.square(g)), jnp.sum(jnp.square(r)),
                          jnp.sum(jnp.square(g - r))])

    def compare(got, ref):
        """Per group the norms of ``got`` (on the device, one entry a
        layer), of ``ref`` (on the host, sent up a leaf at a time) and of
        their difference."""
        sq = {}
        for path, g in paths(got).items():
            layer = path.split("/")[0]
            kind = (pattern[int(layer[len("layer_"):])]
                    if layer.startswith("layer_") else "")
            acc = sq.setdefault(group_of(path, kind), np.zeros(3))
            acc += np.asarray(sums(g, ref[path]), np.float64)
        return {k: tuple(float(x) for x in np.sqrt(v))
                for k, v in sq.items()}

    # the weights the trainer started from: the same seed, the same init.
    # Whatever the seed draws (keys, token ids, the bias) is an ARGUMENT of
    # every function compiled here: closed over, it would be a constant of
    # the program, and every seed would compile its own
    init = jax.jit(lambda key, drop, b: job["model"].init(
        {"params": key, "dropout": drop}, b, train=False))
    seeds = (jax.random.PRNGKey(job["seed"]),
             jax.random.PRNGKey(job["seed"] + 1))
    variables = init(*seeds, batch)
    stats = dict(variables["batch_stats"])
    names = sorted((k[len("bias_"):] for k in stats if k.startswith("bias_")),
                   key=lambda n: int(n[len("layer_"):]))
    keys = jax.random.split(jax.random.PRNGKey(job["seed"] + 2), len(names))
    for name, key in zip(names, keys):
        stats[f"bias_{name}"] = BIAS_SCALE * jax.random.normal(
            key, stats[f"bias_{name}"].shape, jnp.float32)
    biases = {name: stats[f"bias_{name}"] for name in names}
    abstract = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        variables["params"])
    # one entry a layer for the reference; the stacked tree goes
    by_layer = layer_trees(variables["params"], pattern)
    jax.block_until_ready(by_layer)
    for k, v in variables["params"].items():
        if k.startswith("layers_"):
            jax.tree.map(lambda a: a.delete(), v)
    del variables

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
                loss_fn, has_aux=True)).lower(abstract, stats, batch)

    def reference(label):
        t0 = time.monotonic()
        # every document padded (masked) to one length, the longest's
        # rounded up to the reference's row block: one shape to compile
        longest = -(-max(len(d) for d in docs) // Q_BLOCK) * Q_BLOCK
        loss, grads = _reference.loss_and_grads(
            by_layer, lm, share, biases, docs, q_block=Q_BLOCK,
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

    probe = os.environ.get("NEMOTRON_PROBE_PRODUCTS")
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
             jax.tree.map(jnp.asarray, _unflatten(low_grads)), TOL_SHIPPED)
        del low_grads
    # the reference is done: its one-entry-a-layer parameters go, and the
    # program's stacked ones are made from the same seed
    jax.tree.map(lambda a: a.delete(), by_layer)
    params = init(*seeds, batch)["params"]

    def program(label, compiled, tol):
        t0 = time.monotonic()
        (loss, new_stats), grads = compiled.result()(params, stats, batch)
        # of the same train-mode pass: the bias's step on each layer
        moved = {n: float(jnp.max(jnp.abs(new_stats[f"bias_{n}"] - biases[n])))
                 for n in names}
        out = rung(label, float(loss), layer_trees(grads, pattern), tol)
        jax.tree.map(lambda a: a.delete(), grads)
        say(f"parity: program {label} run and compared in "
            f"{time.monotonic() - t0:.1f}s")
        return out, moved

    shipped, moved_shipped = program("as_shipped", compiled[0], TOL_SHIPPED)
    highest, moved_highest = program("highest", compiled[1], TOL_F32)
    out = {"as_shipped": shipped, "highest": highest}
    # exactly one step of the update speed, up or down, on every expert
    # layer (an expert whose load IS the mean stays: not every entry moves)
    out["bias_step"] = moved_shipped
    bias_ok = len(names) == pattern.count("E") and all(
        abs(m - BIAS_UPDATE_SPEED) <= 1e-6
        for moved in (moved_shipped, moved_highest) for m in moved.values())
    say(f"parity: the bias's step by layer {out['bias_step']} "
        f"(want {BIAS_UPDATE_SPEED:g} on each of {pattern.count('E')})")
    out["ok"] = bias_ok and all(out[k]["dev"] <= out[k]["tol"]
                                for k in ("highest", "as_shipped"))
    return out


def _unflatten(by_path):
    """``{"a/b": leaf}`` back to a nested dict."""
    tree = {}
    for path, leaf in by_path.items():
        node = tree
        *parents, last = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[last] = leaf
    return tree


_lm.reference_parity = reference_parity
_lm._counts = _counts


def run(ctx):
    import numpy as np

    config = copy.deepcopy(ctx["config"])
    arch = config["NeuralNetwork"]["Architecture"]
    arch["nemotron_h"] = {k: v for k, v in config.items()
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
    epochs = result["facts"]["epochs"]
    resets_ok = bool(epochs)
    for e in epochs:
        recs = by_epoch.get(e["epoch"], [])
        moe = [r["moe"] for r in recs if "moe" in r]
        for key in ("load_all_max_over_mean", "bias_abs_max"):
            vals = [m[key] for m in moe if key in m]
            e[f"moe_{key}"] = float(np.mean(vals)) if vals else None
        ssm = [r["ssm"] for r in recs if "ssm" in r]
        for key in ("chunks", "chunks_padding", "resets"):
            e[f"ssm_{key}"] = (float(sum(s[key] for s in ssm))
                               if ssm else None)
        # a state starts once a real graph, in every counted dispatch
        resets_ok = resets_ok and len(ssm) == len(recs) > 0 and all(
            r["ssm"]["resets"] == r["num_graphs"] for r in recs)
    grew = [e["moe_bias_abs_max"] for e in epochs]
    ok = bool(epochs) and all(
        e["moe_load_all_max_over_mean"] is not None for e in epochs) and (
            grew[0] is not None and grew[0] > 0 and grew[-1] >= grew[0])
    ctx["say"](f"bias: |b| max by counted epoch "
               f"{[round(g, 4) if g is not None else None for g in grew]}; "
               f"all-expert load max/mean "
               f"{[e['moe_load_all_max_over_mean'] for e in epochs[:3]]}")
    ctx["say"](f"ssm: chunks / of them padding / resets by counted epoch "
               f"{[(e['ssm_chunks'], e['ssm_chunks_padding'], e['ssm_resets']) for e in epochs[:3]]}"
               f"; graphs {[e['graphs'] for e in epochs[:3]]}")
    if not ok:
        ctx["say"]("CHECK FAILED bias: the step records carry no "
                   "full-width counts, or |b| did not grow")
    if not resets_ok:
        ctx["say"]("CHECK FAILED ssm: a counted dispatch has no ssm block, "
                   "or its resets are not its real graphs")
    result["correct"] = bool(result["correct"] and ok and resets_ok)
    return result
