"""Plain reference of the LFM2-MoE layer stack: forward, loss and every
gradient.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
one document at a time, no kernels, no batching, no cache, no boundary
logic (a document's convolution is left-padded with zeros) and nothing of
``hydragnn_tpu``: plain dicts in, plain arrays out.  It follows the
published ``config.json`` (LiquidAI/LFM2-24B-A2B, ``model_type``
lfm2_moe).  With ``RMS(x; g) = x / sqrt(mean(x^2) + norm_eps) * g``, every
layer ``l`` is two halves behind pre-norms and residuals::

    x <- x + Op_l(RMS(x; g_op))          x <- x + FF_l(RMS(x; g_ff))

* ``Op``, ``layer_types[l] == "conv"``, the short convolution (``d`` the
  hidden size, ``K = conv_L_cache``): ``[B | C | X] = u W_in`` (``d ->
  3d``, thirds in this order); ``z = B * X``; along the positions ``t`` of
  the document, per channel, ``v_t = sum_{j=0..K-1} w[K-1-j] z_{t-j}``
  with zeros before the document (torch's depthwise ``Conv1d``, weight
  ``[d, 1, K]``, left padding ``K - 1``, kept here as ``[K, d]``); ``y = C
  * v``; ``Op = y W_out``.  No bias (``conv_bias`` false), no activation.
* ``Op``, ``"full_attention"``: ``q = u W_q``, ``k = u W_k``, ``v = u
  W_v``; ``q <- RMS(q; g_q)``, ``k <- RMS(k; g_k)`` over each head's
  channels (one scale for all query heads, one for all key heads); rotary
  over all the head's channels on ``q`` and ``k``; causal, grouped-query,
  scale ``1 / sqrt(head_dim)``; ``Op = o W_o``.  No gate, no bias.
* ``FF``, layers ``< num_dense_layers``: ``(silu(u W1) * (u W3)) W2``.
* ``FF``, the other layers: ``s = sigmoid(u W_r)`` over all the experts;
  the ``num_experts_per_tok`` largest of ``s + b`` are selected (``b`` the
  expert bias); ``w = s[sel] / (sum s[sel] + 1e-6)`` times
  ``routed_scaling_factor``; ``FF = sum over the held selected e of w_e
  (silu(u W1_e) * (u W3_e)) W2_e``.  No shared expert.

Then ``RMS(x; g_out)`` and the logits ``. E^T`` with the embedding's own
table ``E`` (tied), next-token cross-entropy.  Where the config is silent
the forms are named in ``ASSUMED``.  It takes the share description the
program takes (experts held and their offset, the rows of the vocabulary)
and computes exactly that share: what the absent experts would add is left
out, and the partial result goes on to the next layer.  ``whole_share`` is
the uncut model.

The expert bias is an INPUT here (``biases``: layer name -> [E]): state
that no gradient moves; its update rule is the program's
(models/lfm2_moe.py) and the tests'.

``params`` is a nested dict of arrays, named as the program's own tree:

    embed                                   [V, D]   (also the head)
    layer_<l>/op/{norm, w_in, conv_w, w_out}         a ``conv`` layer
    layer_<l>/op/{norm, wq, wk, wv, q_norm, k_norm, wo}
                                                     a ``full_attention`` one
    layer_<l>/ffn/{norm, w1, w3, w2}                 the dense layers
    layer_<l>/moe/{norm, router, experts_w1, experts_w3, experts_w2}
    final_norm                              [D]

A copy of this file lives in the program's tree
(``hydragnn_tpu/models/lfm2_moe_reference.py``); tests/test_lfm2_moe.py
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
    "tie_word_embeddings true: the key is absent from config.json and the "
    "family's class default ties the head to the embedding (ONE table, its "
    "gradient the sum of both uses)",
    "head_dim = hidden_size / num_attention_heads = 64 (not given)",
    "the thirds of the input product in the order B | C | X, and the "
    "convolution over B * X (the family's modelling code)",
    "rotary pairs dim i with i + head_dim / 2 (rotate_half) over all the "
    "head's dims; a fixed permutation of the columns of W_q and W_k gives "
    "the interleaved pairing, so with seeded weights it is the same model",
    "the routing weights are renormalised over sum + 1e-6 (the family's "
    "block; DeepSeek-V3's, which the other sigmoid routers here follow, "
    "adds 1e-20)",
    "the expert bias (use_expert_bias) starts at zero and steps by 0.001 x "
    "sign(mean load - load) after a train step: the config names the "
    "buffer and not the recipe that moves it; this is DeepSeek-V3's rule "
    "and speed, the one such rule the repository has",
    "no auxiliary balance loss",
)


def whole_share(cfg):
    """The share that holds everything: the uncut model."""
    return {"num_experts_total": cfg["num_experts"], "expert_offset": 0,
            "vocab_total": cfg["vocab_size"], "vocab_offset": 0}


def head_dim(cfg):
    return int(cfg.get("head_dim")
               or cfg["hidden_size"] // cfg["num_attention_heads"])


def apply_rotary(x, positions, theta):
    """Rotate ALL dims of ``x`` [L, heads, rot] by the position (Hugging
    Face's ``rotate_half`` pairing: dim i with i + rot/2)."""
    rot = x.shape[-1]
    inv_freq = 1.0 / float(theta) ** (
        np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = (positions.astype(jnp.float32)[:, None]
           * jnp.asarray(inv_freq, jnp.float32)[None, :])
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def short_conv(p, cfg, u):
    """The double-gated short convolution of one document ``u`` [L, D]
    (the normed input)."""
    L, d = u.shape
    taps = p["conv_w"].shape[0]
    proj = _mm(u, p["w_in"])
    b, c, x = proj[:, :d], proj[:, d:2 * d], proj[:, 2 * d:]
    z = jnp.concatenate([jnp.zeros((taps - 1, d), jnp.float32), b * x])
    # z[taps - 1 + t - j] is z_{t-j}; zeros before the document
    v = sum(p["conv_w"][taps - 1 - j] * z[taps - 1 - j:taps - 1 - j + L]
            for j in range(taps))
    return _mm(c * v, p["w_out"])


def attention(p, cfg, u, q_block=None):
    """Grouped-query attention of one document ``u`` [L, D] (the normed
    input) with a learned RMS norm over each query and key head."""
    L, hd, eps = u.shape[0], head_dim(cfg), cfg["norm_eps"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    theta = cfg["rope_parameters"]["rope_theta"]
    pos = jnp.arange(L)
    q = rms_norm(_mm(u, p["wq"]).reshape(L, heads, hd), p["q_norm"], eps)
    k = rms_norm(_mm(u, p["wk"]).reshape(L, kv, hd), p["k_norm"], eps)
    q, k = apply_rotary(q, pos, theta), apply_rotary(k, pos, theta)
    v = _mm(u, p["wv"]).reshape(L, kv, hd)
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)

    def rows(q_rows, pos_rows):
        seen = pos_rows[:, None] - pos[None, :] >= 0
        s = jnp.einsum("qhd,khd->hqk", _r(q_rows), _r(k)) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _r(w), _r(v))

    if q_block and L > q_block and L % q_block == 0:
        # the same rows, ``q_block`` at a time, so that the [heads, L, L]
        # scores of a long document never exist at once
        o = jax.lax.map(
            jax.checkpoint(lambda lo: rows(
                jax.lax.dynamic_slice_in_dim(q, lo, q_block),
                lo + jnp.arange(q_block))),
            jnp.arange(0, L, q_block)).reshape(L, heads, hd)
    else:
        o = rows(q, pos)
    return _mm(o.reshape(L, heads * hd), p["wo"])


def gated_mlp(u, w1, w3, w2):
    return _mm(jax.nn.silu(_mm(u, w1)) * _mm(u, w3), w2)


def routing(p, cfg, u, bias):
    """(expert ids [L, k], weights [L, k]) over ALL the experts: sigmoid
    scores, the k largest of ``score + bias`` selected, the weights the
    selected experts' unbiased scores, renormalised (+ 1e-6), times the
    routed scaling factor."""
    scores = jax.nn.sigmoid(u.astype(jnp.float32) @ p["router"])
    _, ids = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg.get("norm_topk_prob", True):
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-6)
    return ids, top * cfg.get("routed_scaling_factor", 1.0)


def moe(p, cfg, share, u, bias):
    """The held experts' part of the routed sum; nothing else: a token none
    of whose experts is held gets zero."""
    ids, weights = routing(p, cfg, u, bias)
    held = share["expert_offset"] + jnp.arange(p["experts_w1"].shape[0])
    # [L, held]: the weight a token gives each held expert, 0 where it did
    # not select it; every held expert computes every token
    w = jnp.sum(jnp.where(ids[:, :, None] == held, weights[:, :, None], 0.0),
                axis=1)
    hidden = (
        jax.nn.silu(jnp.einsum("ld,edf->elf", _r(u), _r(p["experts_w1"])))
        * jnp.einsum("ld,edf->elf", _r(u), _r(p["experts_w3"])))
    return jnp.einsum("le,eld->ld", w, jnp.einsum(
        "elf,efd->eld", _r(hidden), _r(p["experts_w2"])))


def layer_forward(p, cfg, share, x, bias=None, q_block=None):
    """One layer: the operator by ``p["op"]``'s leaves, the feed-forward
    dense where ``p`` has ``ffn``, else experts under ``bias``."""
    eps = cfg["norm_eps"]
    op = p["op"]
    u = rms_norm(x, op["norm"], eps)
    h = x + (short_conv(op, cfg, u) if "conv_w" in op
             else attention(op, cfg, u, q_block))
    if "ffn" in p:
        f = p["ffn"]
        return h + gated_mlp(rms_norm(h, f["norm"], eps),
                             f["w1"], f["w3"], f["w2"])
    m = p["moe"]
    return h + moe(m, cfg, share, rms_norm(h, m["norm"], eps), bias)


def next_nll(h, norm, table, ids, length, cfg, share):
    """Sum over the first ``length - 1`` positions of the cross-entropy of
    position i's logits (``h`` normed, times the table transposed) against
    id i + 1."""
    logits = _mm(rms_norm(h, norm, cfg["norm_eps"]), table.T)
    logp = jax.nn.log_softmax(logits[:-1].astype(jnp.float32), axis=-1)
    labels = ids[1:] - share["vocab_offset"]
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(jnp.arange(ids.shape[0] - 1) < length - 1,
                             nll, 0.0))


def document_pieces(cfg, share, q_block=None):
    """The pieces a document goes through, each a compiled function of
    arrays alone: layers of one shape share ONE program, forward and
    backward, whatever the bias, the weights or the ids are.  Each is under
    ``jax.checkpoint``: it keeps only its inputs for the backward pass and
    computes its forward again there."""
    return {
        "layer": jax.jit(jax.checkpoint(
            lambda p, x, b: layer_forward(p, cfg, share, x, b, q_block))),
        "next": jax.jit(jax.checkpoint(
            lambda h, norm, table, ids, n: next_nll(
                h, norm, table, ids, n, cfg, share))),
    }


def document_nll(params, cfg, share, biases, ids, length, pieces):
    """Sum over the first ``length - 1`` positions of the next-token
    cross-entropy.  ``ids`` may be padded past ``length``: both operators
    are causal, so the padding stays out of every counted position.  The
    table is read twice, and its gradient is the sum of both uses."""
    x = params["embed"][ids - share["vocab_offset"]]
    for i in range(cfg["num_hidden_layers"]):
        name = f"layer_{i}"
        x = pieces["layer"](params[name], x, biases.get(name))
    return pieces["next"](x, params["final_norm"], params["embed"], ids,
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
