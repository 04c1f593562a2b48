"""Plain reference of the Nemotron-H layer stack: forward, loss and every
gradient.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
one document at a time, no kernels, no batching, no chunking, no cache, and
nothing of ``hydragnn_tpu``: plain dicts in, plain arrays out.  It follows
the published ``config.json`` (nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16,
``model_type`` nemotron_h).  Every layer is ONE mixer behind a pre-norm and
a residual, ``x <- x + Mixer(RMSNorm(x))``, the mixer read from the pattern
string (``hybrid_override_pattern``, as held):

* ``M``, Mamba-2: ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv1d(xBC))``
  (depthwise, causal, kernel ``conv_kernel``, with bias); ``[x | B | C] =
  xBC``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head
  with its group's ``B``, ``C`` the recurrence ``S_t = exp(dt_t A) S_{t-1} +
  dt_t x_t B_t^T``, ``y_t = S_t C_t + D x_t``, written below as that loop
  over ``t``; ``y = GroupRMSNorm(y * silu(z))`` over groups of
  ``heads x head_dim / n_groups`` channels; ``Mixer = y W_out``.
* ``*``, attention: grouped-query, causal, scale ``1/sqrt(head_dim)``, no
  bias and NO positional term.
* ``E``, LatentMoE: sigmoid scores over all the experts, the
  ``num_experts_per_tok`` largest of ``score + bias`` selected, weights the
  selected experts' unbiased scores renormalised (+ 1e-20) times
  ``routed_scaling_factor``; the experts live in a ``moe_latent_size``-wide
  space: ``l = u W_down``, ``E_e(l) = relu(l W1_e)^2 W2_e``, ``Mixer =
  (sum_e w_e E_e(l)) W_up + relu(u V1)^2 V2`` (the shared expert, in the
  hidden space).

Where the config is silent the forms are named in ``ASSUMED``.  It takes
the share description the program takes (experts held and their offset,
the rows of the vocabulary; the state-space heads, groups and attention
heads held ARE the parameters' shapes) and computes exactly that share:
what the absent experts would add is left out, and the partial result goes
on to the next layer.  ``whole_share`` is the uncut model.

The correction bias is an INPUT here (``biases``: layer name -> [E]): state
that no gradient moves; its update rule is the program's
(models/nemotron_h.py) and the tests'.

``params`` is a nested dict of arrays, one entry a layer whatever the
program scans or unrolls (models/nemotron_h.py ``layer_trees`` gives it
from the program's tree):

    embed                               [V, D]
    layer_<l>/{norm, in_proj, conv_w, conv_b, A_log, D, dt_bias,
               gate_norm, out_proj}                          an ``M`` layer
    layer_<l>/{norm, wq, wk, wv, wo}                         a ``*`` layer
    layer_<l>/{norm, router, down, experts_w1, experts_w2, up,
               shared_w1, shared_w2}                         an ``E`` layer
    final_norm                          [D]
    head                                [D, V]

A copy of this file lives in the program's tree
(``hydragnn_tpu/models/nemotron_h_reference.py``); tests/test_nemotron_h.py
holds the two byte-identical.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

# None, or a dtype every matrix product's operands are rounded to first:
# the benchmark's reading of "the nearest precision below" (PERF.md)
PRODUCT_DTYPE = None


def _r(a):
    if PRODUCT_DTYPE is None:
        return a
    return a.astype(PRODUCT_DTYPE).astype(jnp.float32)


def _mm(a, b):
    return _r(a) @ _r(b)


ASSUMED = (
    "the router is DeepSeek-V3's, whose key names (n_group, topk_group, "
    "norm_topk_prob, routed_scaling_factor) the config uses: sigmoid "
    "scores, selection under a correction bias that carries no gradient, "
    "weights from the unbiased scores; one group (n_group 1, topk_group 1)",
    "the correction bias starts at zero and steps by 0.001 x sign(mean "
    "load - load) after a train step (DeepSeek-V3's bias update speed; "
    "not in the config)",
    "attention carries no rotary or other positional term (position comes "
    "from the state-space layers); rope_theta and partial_rotary_factor "
    "are unused keys",
    "dt is not clamped above after its softplus: time_step_min, "
    "time_step_max and time_step_floor set the initialisation of dt_bias "
    "only (the inverse softplus of a log-uniform draw in [0.001, 0.1], "
    "floored at 1e-4)",
    "A_log starts at log(uniform(1, 16)), D at 1; matrices at normal(0, "
    "fan_in^-0.5), the embedding at normal(0, 1), norms at 1, the "
    "convolution at uniform(+-conv_kernel^-0.5) with a zero bias "
    "(rescale_prenorm_residual is an initialisation rule of the released "
    "checkpoint and is not applied to seeded weights)",
    "the shared expert has width moe_shared_expert_intermediate_size, the "
    "same ungated relu^2 form, and reads and writes the hidden space",
    "the gated norm multiplies by silu(z) BEFORE normalising (Mamba-2's "
    "norm_before_gate false), in groups of heads x head_dim / n_groups "
    "channels",
    "no auxiliary balance loss; no multi-token-prediction module "
    "(num_nextn_predict_layers 0 here)",
)


def whole_share(cfg):
    """The share that holds everything: the uncut model."""
    return {"num_experts_total": cfg["n_routed_experts"], "expert_offset": 0,
            "vocab_total": cfg["vocab_size"], "vocab_offset": 0}


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def causal_conv(x, w, b):
    """Depthwise causal convolution of one document ``x`` [L, C]: ``w`` [K,
    C], ``w[K - 1]`` on the token itself, zeros before the document."""
    taps, length = w.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return b + sum(w[k] * padded[k:k + length] for k in range(taps))


def mamba(p, cfg, u):
    """The Mamba-2 mixer of one document ``u`` [L, D] (the normed input);
    heads and groups held are ``p``'s shapes."""
    length = u.shape[0]
    hd, state = cfg["mamba_head_dim"], cfg["ssm_state_size"]
    heads = p["A_log"].shape[0]
    groups = (p["conv_w"].shape[1] - heads * hd) // (2 * state)
    proj = _mm(u, p["in_proj"])
    z = proj[:, :heads * hd]
    xbc = jax.nn.silu(causal_conv(
        proj[:, heads * hd:-heads], p["conv_w"], p["conv_b"]))
    dt = jax.nn.softplus(proj[:, -heads:] + p["dt_bias"])        # [L, H]
    x = _r(xbc[:, :heads * hd]).reshape(length, heads, hd)
    # every head reads its group's B and C
    b, c = (jnp.repeat(_r(part).reshape(length, groups, state),
                       heads // groups, axis=1)
            for part in (xbc[:, heads * hd:heads * hd + groups * state],
                         xbc[:, heads * hd + groups * state:]))
    a = -jnp.exp(p["A_log"])

    def step(s, row):
        xt, bt, ct, dtt = row
        s = (jnp.exp(dtt * a)[:, None, None] * s
             + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return s, jnp.einsum("hps,hs->hp", s, ct)

    _, y = jax.lax.scan(step, jnp.zeros((heads, hd, state), jnp.float32),
                        (x, b, c, dt))
    y = (y + p["D"][:, None] * x).reshape(length, heads * hd)
    y = y * jax.nn.silu(z)
    # the grouped norm: each group of channels by its own mean square
    g = y.reshape(length, groups, heads * hd // groups)
    g = g * jax.lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + cfg["layer_norm_epsilon"])
    return _mm(g.reshape(length, heads * hd) * p["gate_norm"], p["out_proj"])


def attention(p, cfg, u, q_block=None):
    """Grouped-query causal attention of one document, no positional term;
    the heads held are ``p``'s shapes."""
    length, hd = u.shape[0], cfg["head_dim"]
    heads, kv = p["wq"].shape[1] // hd, p["wk"].shape[1] // hd
    pos = jnp.arange(length)
    q = _mm(u, p["wq"]).reshape(length, heads, hd)
    k = jnp.repeat(_mm(u, p["wk"]).reshape(length, kv, hd), heads // kv,
                   axis=1)
    v = jnp.repeat(_mm(u, p["wv"]).reshape(length, kv, hd), heads // kv,
                   axis=1)

    def rows(q_rows, pos_rows):
        seen = pos_rows[:, None] - pos[None, :] >= 0
        s = jnp.einsum("qhd,khd->hqk", _r(q_rows), _r(k)) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _r(w), _r(v))

    if q_block and length > q_block and length % q_block == 0:
        # the same rows, ``q_block`` at a time, so that the [heads, L, L]
        # scores of a long document never exist at once
        o = jax.lax.map(
            jax.checkpoint(lambda lo: rows(
                jax.lax.dynamic_slice_in_dim(q, lo, q_block),
                lo + jnp.arange(q_block))),
            jnp.arange(0, length, q_block)).reshape(length, heads, hd)
    else:
        o = rows(q, pos)
    return _mm(o.reshape(length, heads * hd), p["wo"])


def routing(p, cfg, u, bias):
    """(expert ids [L, k], weights [L, k]) over ALL the experts."""
    scores = jax.nn.sigmoid(u.astype(jnp.float32) @ p["router"])
    _, ids = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg.get("norm_topk_prob", True):
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return ids, top * cfg.get("routed_scaling_factor", 1.0)


def latent_moe(p, cfg, share, u, bias, shared=True):
    """The held experts' part of the routed sum, in the latent space and
    brought back up, plus the shared expert (every chip computes it alike;
    ``shared=False`` leaves it out so that shares can be added up: the
    up-projection is linear, so the shares' parts add up through it)."""
    ids, weights = routing(p, cfg, u, bias)
    held = share["expert_offset"] + jnp.arange(p["experts_w1"].shape[0])
    # [L, held]: the weight a token gives each held expert, 0 where it did
    # not select it; every held expert computes every token
    w = jnp.sum(jnp.where(ids[:, :, None] == held, weights[:, :, None], 0.0),
                axis=1)
    latent = _mm(u, p["down"])
    hidden = relu2(jnp.einsum("ld,edf->elf", _r(latent),
                              _r(p["experts_w1"])))
    routed = jnp.einsum("le,eld->ld", w, jnp.einsum(
        "elf,efd->eld", _r(hidden), _r(p["experts_w2"])))
    out = _mm(routed, p["up"])
    if shared:
        out = out + _mm(relu2(_mm(u, p["shared_w1"])), p["shared_w2"])
    return out


def kind_of(p):
    return "M" if "in_proj" in p else "E" if "router" in p else "*"


def layer_forward(p, cfg, share, x, bias=None, q_block=None):
    """One layer: ``x + Mixer(RMSNorm(x))``, the mixer by ``p``'s leaves."""
    u = rms_norm(x, p["norm"], cfg["layer_norm_epsilon"])
    kind = kind_of(p)
    if kind == "M":
        return x + mamba(p, cfg, u)
    if kind == "E":
        return x + latent_moe(p, cfg, share, u, bias)
    return x + attention(p, cfg, u, q_block)


def next_nll(h, norm, head, ids, length, cfg, share):
    """Sum over the first ``length - 1`` positions of the cross-entropy of
    position i's logits against id i + 1."""
    logits = _mm(rms_norm(h, norm, cfg["layer_norm_epsilon"]), head)
    logp = jax.nn.log_softmax(logits[:-1].astype(jnp.float32), axis=-1)
    labels = ids[1:] - share["vocab_offset"]
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(jnp.arange(ids.shape[0] - 1) < length - 1,
                             nll, 0.0))


def document_pieces(cfg, share, q_block=None):
    """The pieces a document goes through, each a compiled function of
    arrays alone: layers of one kind share ONE program, forward and
    backward, whatever the bias, the weights or the ids are.  Each is under
    ``jax.checkpoint``: it keeps only its inputs for the backward pass and
    computes its forward again there."""
    return {
        "layer": jax.jit(jax.checkpoint(
            lambda p, x, b: layer_forward(p, cfg, share, x, b, q_block))),
        "next": jax.jit(jax.checkpoint(
            lambda h, norm, head, ids, n: next_nll(
                h, norm, head, ids, n, cfg, share))),
    }


def document_nll(params, cfg, share, biases, ids, length, pieces):
    """Sum over the first ``length - 1`` positions of the next-token
    cross-entropy.  ``ids`` may be padded past ``length``: every mixer is
    causal, so the padding stays out of every counted position."""
    x = params["embed"][ids - share["vocab_offset"]]
    for i in range(len(cfg["hybrid_override_pattern"])):
        name = f"layer_{i}"
        x = pieces["layer"](params[name], x, biases.get(name))
    return pieces["next"](x, params["final_norm"], params["head"], ids,
                          length)


def loss_and_grads(params, cfg, share, biases, documents, q_block=None,
                   pad_to=None):
    """(loss, gradient of loss): the mean next-token cross-entropy over
    every position of every document that has a successor.  One document
    at a time, the sums accumulated.  ``pad_to(L)`` may round a length up
    (the last id appended, masked out) so that few distinct shapes are
    compiled.  A document is differentiated piece by piece
    (``document_pieces``)."""
    with jax.default_matmul_precision("highest"):
        docs = [np.asarray(d, np.int32) for d in documents if len(d) >= 2]
        count = sum(len(d) - 1 for d in docs)
        pieces = document_pieces(cfg, share, q_block)

        def scaled(p, ids, n):
            nll = document_nll(p, cfg, share, biases, ids, n, pieces)
            return nll / count, nll

        # the running sum is donated: a document's gradient is added in place
        add = jax.jit(lambda acc, g: jax.tree.map(jnp.add, acc, g),
                      donate_argnums=(0,))
        total, grads = 0.0, jax.tree.map(jnp.zeros_like, params)
        for doc in docs:
            n = len(doc)
            if pad_to is not None:
                doc = np.concatenate(
                    [doc, np.full(pad_to(n) - n, doc[-1], np.int32)])
            (_, nll), g = jax.value_and_grad(scaled, has_aux=True)(
                params, jnp.asarray(doc), n)
            grads = add(grads, g)
            total += float(nll)
    return total / count, grads
