"""train_epochs_mla — the language-model epoch driver for the latent-
attention stack (``model_type: "GlmMoeLite"``).

Everything that times the job is the stock driver's and everything that
sums the step records the language-model driver's (``train_epochs.py`` and
``train_epochs_lm.py``, loaded by path and left untouched: ``_run``, the
region clock, the timed loader, the profiler window, the median epoch
rate, checks (b)-(g)).  What differs is this file:

* ``run`` copies the configuration's top-level keys (the public
  ``config.json``'s names) to ``Architecture.glm_moe_lite``, where this
  stack reads them.
* ``facts["lm"]`` is ``mla_counts.lm_facts`` (the visible pairs at this
  stack's head shapes), and each counted epoch gains the step records'
  ``moe.load_all_max_over_mean`` and ``moe.bias_abs_max``.
* ``correct`` (a) is ``reference_parity`` below: after the window, the
  forward and backward pass of the TIMED program
  (``trainer._loss_and_metrics`` in train mode, both heads under
  ``task_weights``; the optimizer is left out: its moments would not fit
  beside the reference's gradients) on the cell's first micro-batch,
  padded to the bucket the timed loader gives it, against the plain
  reference (``reference/glm_moe_lite_reference.py``: float32, "highest",
  one document at a time, attention in query blocks; its gradients wait
  on the host and go back up a leaf at a time for the comparison, which
  runs on the device) on the same seeded weights and the same correction
  bias ``b``: a seeded non-zero one, of the size a window's training
  reaches, so that selection under ``score + b`` with weights from the
  unbiased scores is what is compared.
  Compared: the loss, each head's loss, the global gradient norm, and per
  parameter group the norm of the gradient and the norm of the DIFFERENCE
  of the two gradients over the reference's norm.  Two rungs: the program
  forced to float32 under "highest" (summation order only), and as shipped
  (bfloat16 products).  And, of the same train-mode pass: ``b`` stepped by
  exactly its update speed on every expert layer.
* one more check on the step records: every counted step reported the
  slots on all 64 experts, and ``|b|`` grew over the window.

The comparison has to stay short and its programs small: the check stops
a run at 360 s, and the compile cache it keeps between runs holds 192 MiB
(``JAX_COMPILATION_CACHE_MAX_SIZE`` on the chip's machine), least recently
used out first.  So nothing compiled here closes over a seeded value (it
would be a constant of the program, and every seed would compile its
own), the reference compiles a layer once and not once per layer, and the
reference, the two traces and the two compiles overlap.
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
# instance of the stock driver): its ``_run`` finds the three names it
# looks up in its module at call time replaced by this stack's
_lm = _load("benchmark_mla_lm_train_epochs",
            os.path.join(_HERE, "train_epochs_lm.py"))
_stock = _lm._stock
_counts = _load("benchmark_mla_counts", os.path.join(_BENCH, "mla_counts.py"))
_reference = _load("benchmark_mla_reference", os.path.join(
    _BENCH, "reference", "glm_moe_lite_reference.py"))

# Limits of the comparison, per rung: on the loss and on each head's loss,
# on the whole gradient's difference from the reference's (norm of the
# difference over the reference's norm), and on each parameter group's (its
# norm, its difference).  Each lies between two readings on the v5e
# (PERF.md section 4, PR 32).
#
# Program forced to float32 under "highest" vs the reference: both are true
# float32, and differ by summation order (blocked flash softmax, grouped
# products over sorted rows, sliced feed-forward) and transcendental
# rounding.
TOL_F32 = {"loss": 2e-4, "grad": 2e-4, "group": 1e-3}
# As shipped (bfloat16 operands, float32 accumulation) vs the reference,
# and the reference with every product's operands rounded to float8_e4m3
# (GLM_PROBE_PRODUCTS) vs itself: the readings are in PERF.md's table.  The
# losses carry no limit on this rung (they are printed): float8 products
# move them LESS than bfloat16 ones do (3e-5 against 7e-5), so no limit on
# them tells the two apart; the float32 rung holds the losses.
TOL_SHIPPED = {"loss": None, "grad": 2.5e-2, "group": 2e-1}
Q_BLOCK = 1024          # the reference's attention, rows at a time
BIAS_SCALE = 0.02       # the seeded bias of the comparison: about what
#                         30 train steps of 0.001 reach
BIAS_UPDATE_SPEED = 1e-3        # models/glm_moe_lite.py, ASSUMED


def group_of(path: str) -> str:
    """A parameter's group for the comparison, from its tree path
    (``layer_2/attn/wuq`` -> ``layer_2.mla_up``, ``mtp/layer/moe/router``
    -> ``mtp.router``, ``mtp/eh_proj`` -> ``mtp.eh_proj``)."""
    parts = path.split("/")
    if parts[0] in ("embed", "head", "final_norm"):
        return "embed" if parts[0] == "embed" else "head"
    where = parts[0]
    if where == "mtp":
        if parts[1] != "layer":     # eh_proj and the module's three norms
            return "mtp.eh_proj"
        parts = parts[1:]
    block, leaf = parts[1], parts[-1]
    if block == "attn":
        kind = ("mla_out" if leaf == "wo" else
                "mla_up" if leaf in ("wuq", "wukv") else "mla_down")
    elif block == "moe":
        kind = ("experts" if leaf.startswith("experts_") else
                "shared" if leaf.startswith("shared_") else "router")
    else:
        kind = "ffn"
    return f"{where}.{kind}"


def reference_parity(job, say):
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
    # the bucket of the epoch's largest batch, so that is the timed shape
    nodes = max(b.num_nodes for b in loader)
    spec = next(p for p in loader.pad_specs if p.num_nodes == nodes)
    batch = jax.device_put(collate(samples, spec, job["head_specs"],
                                   *job["slices"]))
    say(f"parity: the first {len(samples)} train documents, "
        f"{sum(s.num_nodes for s in samples)} tokens, in the dispatch "
        f"group's bucket of {spec.num_nodes} nodes")
    docs = [np.asarray(s.x[:, 0], np.int32) for s in samples]
    arch = job["config"]["NeuralNetwork"]["Architecture"]
    lm, share = arch["glm_moe_lite"], arch["share"]
    weight = float(arch["task_weights"][1]) / float(arch["task_weights"][0])

    t_start = time.monotonic()

    def paths(tree):
        return {"/".join(str(getattr(k, "key", k)) for k in path): leaf
                for path, leaf in jax.tree_util.tree_leaves_with_path(tree)}

    def on_host(tree):
        """Leaves by path as numpy, the device's copy dropped: the
        reference's gradients (2.83 GB) wait on the host while the
        program's are made, beside the parameters and that program's
        temporaries."""
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
            acc = sq.setdefault(group_of(path), np.zeros(3))
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
    names = sorted(k[len("bias_"):] for k in stats if k.startswith("bias_"))
    keys = jax.random.split(jax.random.PRNGKey(job["seed"] + 2), len(names))
    for name, key in zip(names, keys):
        stats[f"bias_{name}"] = BIAS_SCALE * jax.random.normal(
            key, stats[f"bias_{name}"].shape, jnp.float32)
    biases = {name: stats[f"bias_{name}"] for name in names}

    def lowered(cfg, precision):
        """The timed program's forward and backward pass, traced here; it
        compiles on a thread of its own while the reference runs."""
        model = create_model(cfg)

        def loss_fn(p, stats, batch):   # the logits stay inside: 3 GB
            loss, (heads, new_stats, _out) = _loss_and_metrics(
                model, cfg, p, stats, batch, True)
            return loss, (heads, new_stats)

        with (jax.default_matmul_precision(precision) if precision
              else contextlib.nullcontext()):
            return jax.jit(jax.value_and_grad(
                loss_fn, has_aux=True)).lower(params, stats, batch)

    def program(label, compiled, tol):
        t0 = time.monotonic()
        (loss, (heads, new_stats)), grads = compiled.result()(
            params, stats, batch)
        # of the same train-mode pass: the bias's step on each layer
        moved = {n: float(jnp.max(jnp.abs(new_stats[f"bias_{n}"] - biases[n])))
                 for n in names}
        out = rung(label, float(loss), [float(h) for h in heads], grads, tol)
        jax.tree.map(lambda a: a.delete(), grads)
        say(f"parity: program {label} run and compared in "
            f"{time.monotonic() - t0:.1f}s")
        return out, moved

    def reference(label):
        t0 = time.monotonic()
        # every document padded (masked) to one length, the longest's
        # rounded up to the reference's row block: one shape to compile
        longest = -(-max(len(d) for d in docs) // Q_BLOCK) * Q_BLOCK
        loss, heads, grads = _reference.loss_and_grads(
            params, lm, share, biases, docs, weight, q_block=Q_BLOCK,
            pad_to=lambda n: longest)
        grads = on_host(grads)
        say(f"parity: {label}, {len(docs)} documents one at a time, each "
            f"padded to {longest} tokens, in {time.monotonic() - t0:.1f}s")
        return loss, heads, grads

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
        ref_loss, ref_heads, ref_grads = ref.result()

    def rung(label, loss, heads, grads, tol):
        groups = compare(grads, ref_grads)
        g_all, r_all, d_all = (
            float(np.sqrt(sum(v[i] ** 2 for v in groups.values())))
            for i in range(3))
        # (deviation, its limit or None) per compared number
        devs = {"loss": (_stock._rel(loss, ref_loss), tol["loss"]),
                "loss_next": (_stock._rel(heads[0], ref_heads[0]),
                              tol["loss"]),
                "loss_next_next": (_stock._rel(heads[1], ref_heads[1]),
                                   tol["loss"]),
                "grad_norm": (_stock._rel(g_all, r_all), tol["grad"]),
                "grad_diff": (d_all / max(r_all, 1e-30), tol["grad"])}
        for k, (g, r, d) in groups.items():
            devs[f"{k}.norm"] = (_stock._rel(g, r), tol["group"])
            devs[f"{k}.diff"] = (d / max(r, 1e-30), tol["group"])
        held = {k: v for k, v in devs.items() if v[1] is not None}
        worst = max(held, key=lambda k: held[k][0] / held[k][1])
        say(f"parity {label}: nearest its limit {worst} "
            f"{devs[worst][0]:.3e} (limit {devs[worst][1]:g}); loss "
            f"{loss:.6f} vs {ref_loss:.6f} ({devs['loss'][0]:.2e}), heads "
            f"{heads[0]:.6f} {heads[1]:.6f} vs {ref_heads[0]:.6f} "
            f"{ref_heads[1]:.6f} ({devs['loss_next'][0]:.2e} "
            f"{devs['loss_next_next'][0]:.2e}), grad norm {g_all:.6g} vs "
            f"{r_all:.6g}, difference {devs['grad_diff'][0]:.3e}")
        say(f"parity {label} by group (norm dev, difference): " + " ".join(
            f"{k}={devs[k + '.norm'][0]:.1e},{devs[k + '.diff'][0]:.1e}"
            for k in sorted(groups)))
        return {"dev": devs[worst][0], "worst": worst,
                "tol": devs[worst][1], "loss": loss, "ref_loss": ref_loss,
                "loss_dev": max(devs[k][0] for k in (
                    "loss", "loss_next", "loss_next_next")),
                "grad_diff": devs["grad_diff"][0],
                "group_diff_max": max(devs[k + ".diff"][0] for k in groups)}

    shipped, moved_shipped = program("as_shipped", compiled[0], TOL_SHIPPED)
    highest, moved_highest = program("highest", compiled[1], TOL_F32)
    out = {"as_shipped": shipped, "highest": highest}
    # exactly one step of the update speed, up or down, on every expert
    # layer (an expert whose load IS the mean stays: not every entry moves)
    out["bias_step"] = moved_shipped
    bias_ok = bool(names) and all(
        abs(m - BIAS_UPDATE_SPEED) <= 1e-6
        for moved in (moved_shipped, moved_highest) for m in moved.values())
    say(f"parity: the bias's step by layer {out['bias_step']} "
        f"(want {BIAS_UPDATE_SPEED:g} on each)")
    probe = os.environ.get("GLM_PROBE_PRODUCTS")
    if probe:
        # the builder's reading of "the nearest precision below": the
        # reference with every product's operands rounded to ``probe``
        # against the same reference gradients; refuses nothing
        _reference.PRODUCT_DTYPE = jnp.dtype(probe)
        try:
            low = reference(f"reference with {probe} products")
        finally:
            _reference.PRODUCT_DTYPE = None
        rung(f"reference_in_{probe}", *low, TOL_SHIPPED)
    out["ok"] = bias_ok and all(out[k]["dev"] <= out[k]["tol"]
                                for k in ("highest", "as_shipped"))
    return out


_lm.reference_parity = reference_parity
_lm._counts = _counts


def run(ctx):
    import numpy as np

    config = copy.deepcopy(ctx["config"])
    arch = config["NeuralNetwork"]["Architecture"]
    arch["glm_moe_lite"] = {k: v for k, v in config.items()
                            if k not in _lm._HF_SKIP}
    arch["share"] = config["share"]
    config["corpus"]["params"]["vocab_size"] = config["vocab_size"]
    _stock._run = _lm._run
    result = _stock.run({**ctx, "config": config})

    # the step records once more, for what only this stack reports
    by_epoch = {}
    for ev in _stock._read_events(os.path.join(ctx["workdir"], "logs")):
        if ev.get("event") == "step" and "moe" in ev:
            by_epoch.setdefault(ev["epoch"], []).append(ev["moe"])
    epochs = result["facts"]["epochs"]
    for e in epochs:
        moe = by_epoch.get(e["epoch"], [])
        for key in ("load_all_max_over_mean", "bias_abs_max"):
            vals = [m[key] for m in moe if key in m]
            e[f"moe_{key}"] = float(np.mean(vals)) if vals else None
    grew = [e["moe_bias_abs_max"] for e in epochs]
    ok = bool(epochs) and all(
        e["moe_load_all_max_over_mean"] is not None for e in epochs) and (
            grew[0] is not None and grew[0] > 0 and grew[-1] >= grew[0])
    ctx["say"](f"bias: |b| max by counted epoch "
               f"{[round(g, 4) if g is not None else None for g in grew]}; "
               f"all-expert load max/mean "
               f"{[e['moe_load_all_max_over_mean'] for e in epochs[:3]]}")
    if not ok:
        ctx["say"]("CHECK FAILED bias: the step records carry no "
                   "full-width counts, or |b| did not grow")
    result["correct"] = bool(result["correct"] and ok)
    return result
