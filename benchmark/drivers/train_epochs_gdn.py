"""train_epochs_gdn — the language-model epoch driver for the Gated DeltaNet
stack (``model_type: "Qwen3Next"``).

Everything that times the job is the stock driver's and everything that
sums the step records the language-model driver's (``train_epochs.py`` and
``train_epochs_lm.py``, loaded by path and left untouched: ``_run``, the
region clock, the timed loader, the profiler window, the median epoch
rate, checks (b)-(g)).  What differs is this file:

* ``run`` copies the configuration's top-level keys (the public
  ``config.json``'s names) to ``Architecture.qwen3_next``, where this stack
  reads them.
* ``facts["lm"]`` is ``gdn_counts.lm_facts`` (the rule's held shapes), and
  each counted epoch gains the step records' ``gdn`` block (``chunks``,
  ``chunks_padding``, ``resets``).
* ``correct`` (a) is ``reference_parity`` below, the comparison
  ``train_epochs_sconv.py`` makes: AFTER the window, the forward and
  backward pass of the TIMED program (``trainer._loss_and_metrics`` in
  train mode; the optimizer is left out) on the cell's first micro-batch,
  padded to the dispatch group's shape, against the plain reference
  (``reference/qwen3_next_reference.py``: float32, "highest", one document
  at a time with no boundary logic at all, the recurrence one token a step,
  attention in query blocks) on the same seeded weights.  Compared: the
  loss, the global gradient norm, and per parameter group (embedding, head,
  per layer the DeltaNet's input products, taps, ``A_log`` / ``dt_bias``,
  gated norm and output product, attention, its head norms, router,
  experts, shared expert) the norm of the gradient and the norm of the
  DIFFERENCE over the reference's norm.  Two rungs: the program forced to
  float32 under "highest" (summation order only: the chunked rule against
  the token-by-token recurrence), and as shipped (bfloat16 products).
* one more check on the step records: every counted dispatch's
  ``gdn.resets`` equals its real graphs times the DeltaNet layers (a state
  was started once a document and a layer, never inside one).

As there, nothing compiled here closes over a seeded value, the reference
compiles each KIND of layer once, the reference, the two traces and the two
compiles overlap, and what the comparison builds is compiled at
``exec_time_optimization_effort`` -1 (programs that run once): ``setup_s``
and the ``setup_*`` readers see the trainer's builds alone.
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
_lm = _load("benchmark_gdn_lm_train_epochs",
            os.path.join(_HERE, "train_epochs_lm.py"))
_stock = _lm._stock
_counts = _load("benchmark_gdn_counts", os.path.join(_BENCH, "gdn_counts.py"))
_reference = _load("benchmark_gdn_reference", os.path.join(
    _BENCH, "reference", "qwen3_next_reference.py"))

# Limits of the comparison, per rung: on the loss, on the whole gradient's
# difference from the reference's (norm of the difference over the
# reference's norm) and on each parameter group's (its norm, its
# difference), the ROUTED groups (router, experts) apart from the others.
# Each lies between two readings on the v5e (PERF.md section 4, PR 44).
#
# Program forced to float32 under "highest" vs the reference: both are true
# float32 and differ by summation order (the chunked rule and its inverse
# against the token-by-token recurrence, blocked softmax, grouped products
# over sorted rows) and transcendental rounding.  With 10 of 512 experts a
# node, four expert layers and ~9-13 k nodes, some node's 10th and 11th
# expert tie to float32 rounding in some seeds, and a swapped expert is a
# different function: Nemotron's limits (22 of 512) leave room for that.
TOL_F32 = {"loss": 2e-4, "grad": 2.5e-3, "group": 5e-3, "routed": 3e-2}
# As shipped (bfloat16 operands, float32 accumulation) vs the reference.
# The loss carries no limit on this rung, as on GLM's, Nemotron's and
# LFM2's (float8 moves it by 1e-4 to 4e-4, bfloat16 by up to 8e-5: too
# near; the float32 rung holds the loss).
TOL_SHIPPED = {"loss": None, "grad": 2.5e-2, "group": 1e-1, "routed": 3e-1}
ROUTED = ("router", "experts")
Q_BLOCK = 1024          # the reference's attention, rows at a time
_GROUPS = {"w_qkvz": "w_in", "w_ba": "w_in", "conv_w": "conv",
           "A_log": "decay", "dt_bias": "decay", "gate_norm": "w_out",
           "w_out": "w_out", "wq": "attn", "wk": "attn", "wv": "attn",
           "wo": "attn", "q_norm": "qk_norm", "k_norm": "qk_norm",
           "router": "router", "experts_w1": "experts",
           "experts_w3": "experts", "experts_w2": "experts",
           "shared_w1": "shared", "shared_w3": "shared",
           "shared_w2": "shared", "shared_gate": "shared"}


def group_of(path: str, kinds=()) -> str:
    """A parameter's group for the comparison, from its tree path
    (``layer_2/mixer/w_qkvz`` -> ``layer_2.w_in``); a half-layer's input
    norm goes with the first matrix that reads it (``kinds``: the held
    layers' kinds, which say whether ``layer_3/mixer/norm`` feeds ``w_in``
    or ``attn``; ``layer_3/moe/norm`` goes with ``router``); the output
    norm with the head it feeds."""
    parts = path.split("/")
    if not parts[0].startswith("layer_"):
        return "embed" if parts[0] == "embed" else "head"
    group = _GROUPS.get(parts[-1]) or {
        "moe": "router",
        "mixer": "w_in" if kinds[int(parts[0][len("layer_"):])]
        == "linear_attention" else "attn"}[parts[1]]
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
    lm, share = arch["qwen3_next"], arch["share"]
    kinds = _reference.layer_kinds(lm)

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
    # Whatever the seed draws (keys, token ids) is an ARGUMENT of every
    # function compiled here: closed over, it would be a constant of the
    # program, and every seed would compile its own
    variables = jax.jit(lambda key, drop, b: job["model"].init(
        {"params": key, "dropout": drop}, b, train=False))(
            jax.random.PRNGKey(job["seed"]),
            jax.random.PRNGKey(job["seed"] + 1), batch)
    params, stats = variables["params"], variables["batch_stats"]

    def lowered(cfg, precision):
        """The timed program's forward and backward pass, traced here; it
        compiles on a thread of its own while the reference runs."""
        model = create_model(cfg)

        def loss_fn(p, stats, batch):   # the logits stay inside
            return _loss_and_metrics(model, cfg, p, stats, batch, True)[0]

        with (jax.default_matmul_precision(precision) if precision
              else contextlib.nullcontext()):
            return jax.jit(jax.value_and_grad(loss_fn)).lower(
                params, stats, batch)

    def program(label, compiled, tol):
        t0 = time.monotonic()
        loss, grads = compiled.result()(params, stats, batch)
        out = rung(label, float(loss), grads, tol)
        jax.tree.map(lambda a: a.delete(), grads)
        say(f"parity: program {label} run and compared in "
            f"{time.monotonic() - t0:.1f}s")
        return out

    def reference(label):
        t0 = time.monotonic()
        # every document padded (masked) to one length, the longest's
        # rounded up to the reference's row block: one shape to compile
        longest = -(-max(len(d) for d in docs) // Q_BLOCK) * Q_BLOCK
        loss, grads = _reference.loss_and_grads(
            params, lm, share, docs, q_block=Q_BLOCK,
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
        routed = [k for k in groups if k.endswith(ROUTED)]
        say(f"parity {label}: worst routed group "
            f"{max(devs[k + '.diff'][0] for k in routed):.3e}, worst other "
            f"group {max(devs[k + '.diff'][0] for k in groups if k not in routed):.3e}")
        return {"dev": devs[worst][0], "worst": worst,
                "tol": devs[worst][1], "loss": loss, "ref_loss": ref_loss,
                "loss_dev": devs["loss"][0],
                "grad_diff": devs["grad_diff"][0],
                "group_diff_max": max(devs[k + ".diff"][0] for k in groups),
                "ok": all(d <= limit for d, limit in held.values())}

    out = {"as_shipped": program("as_shipped", compiled[0], TOL_SHIPPED),
           "highest": program("highest", compiled[1], TOL_F32)}
    probe = os.environ.get("QWEN3_PROBE_PRODUCTS")
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
        low = rung(f"reference_in_{probe}", low_loss,
                   {k: jnp.asarray(v) for k, v in low_grads.items()},
                   TOL_SHIPPED)
        say(f"parity: the reference in {probe} "
            f"{'PASSES' if low['ok'] else 'fails'} the shipped rung")
    out["ok"] = all(out[k]["ok"] for k in ("highest", "as_shipped"))
    return out


_lm.reference_parity = reference_parity
_lm._counts = _counts


def run(ctx):
    config = copy.deepcopy(ctx["config"])
    arch = config["NeuralNetwork"]["Architecture"]
    arch["qwen3_next"] = {k: v for k, v in config.items()
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
    layers = _reference.layer_kinds(arch["qwen3_next"]).count(
        "linear_attention")
    epochs = result["facts"]["epochs"]
    resets_ok = bool(epochs)
    for e in epochs:
        recs = by_epoch.get(e["epoch"], [])
        gdn = [r["gdn"] for r in recs if "gdn" in r]
        for key in ("chunks", "chunks_padding", "resets"):
            e[f"gdn_{key}"] = (float(sum(g[key] for g in gdn))
                               if gdn else None)
        # a state starts once a real graph and a layer, in every counted
        # dispatch
        resets_ok = resets_ok and len(gdn) == len(recs) > 0 and all(
            r["gdn"]["resets"] == layers * r["num_graphs"] for r in recs)
    ctx["say"](f"gdn: chunks / of them padding / resets by counted epoch "
               f"{[(e['gdn_chunks'], e['gdn_chunks_padding'], e['gdn_resets']) for e in epochs[:3]]}"
               f"; graphs {[e['graphs'] for e in epochs[:3]]} x {layers} "
               f"DeltaNet layers")
    if not resets_ok:
        ctx["say"]("CHECK FAILED gdn: a counted dispatch has no gdn block, "
                   "or its resets are not its real graphs times the "
                   "DeltaNet layers")
    result["correct"] = bool(result["correct"] and resets_ok)
    return result
