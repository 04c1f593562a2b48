"""Plain reference of the Qwen3-Next layer stack: forward, loss and every
gradient.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
one document at a time, no kernels, no chunks, no batching, no cache, no
boundary logic (a document starts from a zero state and a zero-padded
convolution) and nothing of ``hydragnn_tpu``: plain dicts in, plain arrays
out.  It follows the published ``config.json``
(Qwen/Qwen3-Next-80B-A3B-Instruct, ``model_type`` qwen3_next) and the
family's modelling code.  With ``zrms(x; w) = x / sqrt(mean(x^2) + eps) *
(1 + w)`` (the zero-centred ``Qwen3NextRMSNorm``: the parameter is ``w``),
every layer ``l`` is two halves behind pre-norms and residuals::

    x <- x + Mixer_l(zrms(x; w_in))          x <- x + MoE(zrms(x; w_post))

* ``Mixer``, ``(l + 1) % full_attention_interval != 0``, Gated DeltaNet
  (``H_k`` key heads, ``H_v`` value heads of ``d_k = d_v``): ``[q | k | v |
  z] = u W_qkvz``, ``[b | a] = u W_ba``; ``c = silu(conv([q | k | v]))``, a
  depthwise causal convolution of ``linear_conv_kernel_dim`` taps without
  bias, zeros before the document (torch's ``Conv1d`` weight ``[C, 1, K]``
  under left padding ``K - 1``, kept here as ``[K, C]``); ``q``, ``k`` of
  ``c`` are l2-normed a head (``x / sqrt(sum x^2 + 1e-6)``) and ``q`` is
  scaled by ``d_k^-0.5``; value head ``h`` reads key head ``h // (H_v /
  H_k)``; ``beta = sigmoid(b)``, ``g = -exp(A_log) * softplus(a +
  dt_bias)``; per value head, from ``S_0 = 0``::

      S' = exp(g_t) S_{t-1}     r_t = v_t - S'^T k_t
      S_t = S' + beta_t k_t r_t^T        o_t = S_t^T q_t

  one token a step of a ``lax.scan``; ``y = o / sqrt(mean(o^2) + eps) * w_n
  * silu(z)`` a head (``Qwen3NextRMSNormGated``: NOT zero-centred); ``Mixer
  = y W_o``.
* ``Mixer``, the other layers, gated attention: ``[q | gate] = u W_q`` (per
  head the first ``head_dim`` columns are ``q``, the next ``head_dim`` the
  gate), ``k = u W_k``, ``v = u W_v``; ``q <- zrms(q; w_q)``, ``k <-
  zrms(k; w_k)`` over each head's channels; rotary on the first ``head_dim
  * partial_rotary_factor`` dims of ``q`` and ``k``; causal, grouped-query,
  scale ``1 / sqrt(head_dim)``; ``o <- o * sigmoid(gate)``; ``Mixer = o
  W_o``.  No bias.
* ``MoE`` (every layer: ``mlp_only_layers`` is empty and
  ``decoder_sparse_step`` 1): ``p = softmax(u W_r)`` over all the experts
  in float32; the ``num_experts_per_tok`` largest; ``w = p[sel] / sum
  p[sel]`` (``norm_topk_prob``); ``sum over the held selected e of w_e
  (silu(u W1_e) * (u W3_e)) W2_e``, plus ``sigmoid(u w_sg) * (silu(u S1) *
  (u S3)) S2``, the shared expert under its own gate.

Then ``zrms(x; w_out)`` and the logits ``. W_head`` (untied), next-token
cross-entropy.  It takes the share description the program takes (experts
held and their offset, the rows of the vocabulary) and computes exactly
that share: what the absent experts would add is left out, and the partial
result goes on to the next layer.  ``whole_share`` is the uncut model.

``params`` is a nested dict of arrays, named as the program's own tree:

    embed                                   [V, D]
    layer_<l>/mixer/{norm, w_qkvz, w_ba, conv_w, A_log, dt_bias,
                     gate_norm, w_out}               a DeltaNet layer
    layer_<l>/mixer/{norm, wq, wk, wv, q_norm, k_norm, wo}
                                                     an attention layer
    layer_<l>/moe/{norm, router, experts_w1, experts_w3, experts_w2,
                   shared_w1, shared_w3, shared_w2, shared_gate}
    final_norm                              [D]
    head                                    [D, V]

A copy of this file lives in the program's tree
(``hydragnn_tpu/models/qwen3_next_reference.py``); tests/test_qwen3_next.py
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
SCAN_BLOCK = 64         # tokens of the recurrence one checkpoint spans


def _r(a):
    if PRODUCT_DTYPE is None:
        return a
    return a.astype(PRODUCT_DTYPE).astype(jnp.float32)


def _mm(a, b):
    return _r(a) @ _r(b)


ASSUMED = (
    "the layer kinds from full_attention_interval: layer l attends where "
    "(l + 1) % interval == 0 (the family's default for layer_types, which "
    "the config does not give)",
    "no multi-token-prediction module: the config has no key for one",
    "no auxiliary load-balancing loss: the config has no coefficient for "
    "one; the loss is next-token cross-entropy alone",
    "rotary pairs dim i with i + rot / 2 (rotate_half) inside the first "
    "rot = head_dim * partial_rotary_factor dims; a fixed permutation of "
    "those columns of W_q and W_k gives the interleaved pairing, so with "
    "seeded weights it is the same model",
    "the l2 norm of q and k adds 1e-6 under the root (the family's "
    "kernels), the gated norm uses rms_norm_eps",
    "A_log and dt_bias start as the family's code draws them (A uniform in "
    "(0, 16), dt log-uniform in [0.001, 0.1] through the inverse "
    "softplus); they are seeded anyway",
)

DEPARTURES = (
    "the columns of W_qkvz stand in the order q | k | v | z and those of "
    "W_ba in the order b | a: the published matrices interleave them by "
    "key head (each key head's q, k, its value heads' v, z); a fixed "
    "permutation of columns, so with seeded weights it is the same model, "
    "and the program holds the same order",
    "W_q's columns stand per head as q | gate (as published)",
    "the recurrence's scan is checkpointed every SCAN_BLOCK tokens: the "
    "backward pass recomputes a block's states instead of keeping one "
    "[H_v, d_k, d_v] state a token (8.6 GB at 4,096 tokens); the "
    "mathematics is token by token, no chunked form",
)


def whole_share(cfg):
    """The share that holds everything: the uncut model."""
    return {"num_experts_total": cfg["num_experts"], "expert_offset": 0,
            "vocab_total": cfg["vocab_size"], "vocab_offset": 0}


def layer_kinds(cfg):
    """``linear_attention`` / ``full_attention`` of every held layer."""
    kinds = cfg.get("layer_types")
    if kinds:
        return list(kinds)[:cfg["num_hidden_layers"]]
    every = int(cfg["full_attention_interval"])
    return ["full_attention" if (i + 1) % every == 0 else "linear_attention"
            for i in range(cfg["num_hidden_layers"])]


def apply_rotary(x, positions, theta, rot):
    """Rotate the first ``rot`` dims of ``x`` [L, heads, head_dim] by the
    position (Hugging Face's ``rotate_half`` pairing inside them: dim i
    with i + rot/2); the other dims pass."""
    inv_freq = 1.0 / float(theta) ** (
        np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = (positions.astype(jnp.float32)[:, None]
           * jnp.asarray(inv_freq, jnp.float32)[None, :])
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :rot // 2], x[..., rot // 2:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def zrms(x, w, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + w)


def l2_norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True)
                             + 1e-6)


def delta_rule(q, k, v, g, beta):
    """The recurrence for one document: ``q`` / ``k`` [L, H, d_k], ``v``
    [L, H, d_v], ``g`` / ``beta`` [L, H] -> [L, H, d_v]."""
    L, heads, dk = k.shape

    def step(s, row):
        qt, kt, vt, gt, bt = row
        decayed = jnp.exp(gt)[:, None, None] * s
        r = vt - jnp.einsum("hkv,hk->hv", _r(decayed), _r(kt))
        s = decayed + (bt[:, None] * kt)[:, :, None] * r[:, None, :]
        return s, jnp.einsum("hkv,hk->hv", _r(s), _r(qt))

    def run(s, rows):
        return jax.lax.scan(step, s, rows)

    s0 = jnp.zeros((heads, dk, v.shape[2]), jnp.float32)
    rows = (q, k, v, g, beta)
    if L > SCAN_BLOCK and L % SCAN_BLOCK == 0:
        _, o = jax.lax.scan(jax.checkpoint(run), s0, jax.tree.map(
            lambda a: a.reshape((L // SCAN_BLOCK, SCAN_BLOCK) + a.shape[1:]),
            rows))
        return o.reshape((L,) + o.shape[2:])
    return run(s0, rows)[1]


def gated_delta_net(p, cfg, u):
    """Gated DeltaNet of one document ``u`` [L, D] (the normed input)."""
    L = u.shape[0]
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    key, value = hk * dk, hv * dv
    taps = p["conv_w"].shape[0]
    proj, ba = _mm(u, p["w_qkvz"]), _mm(u, p["w_ba"])
    qkv, z = proj[:, :2 * key + value], proj[:, 2 * key + value:]
    padded = jnp.concatenate(
        [jnp.zeros((taps - 1, qkv.shape[1]), jnp.float32), qkv])
    # padded[taps - 1 + t - j] is the row t - j; zeros before the document
    c = jax.nn.silu(sum(
        p["conv_w"][taps - 1 - j] * padded[taps - 1 - j:taps - 1 - j + L]
        for j in range(taps)))
    q = l2_norm(c[:, :key].reshape(L, hk, dk)) * dk ** -0.5
    k = l2_norm(c[:, key:2 * key].reshape(L, hk, dk))
    v = c[:, 2 * key:].reshape(L, hv, dv)
    beta = jax.nn.sigmoid(ba[:, :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, hv:] + p["dt_bias"])
    o = delta_rule(jnp.repeat(q, hv // hk, axis=1),
                   jnp.repeat(k, hv // hk, axis=1), v, g, beta)
    y = (o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                           + cfg["rms_norm_eps"])
         * p["gate_norm"] * jax.nn.silu(z.reshape(L, hv, dv)))
    return _mm(y.reshape(L, value), p["w_out"])


def attention(p, cfg, u, q_block=None):
    """Output-gated grouped-query attention of one document ``u`` [L, D]
    (the normed input)."""
    L, hd, eps = u.shape[0], cfg["head_dim"], cfg["rms_norm_eps"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    rot = int(hd * cfg["partial_rotary_factor"])
    pos = jnp.arange(L)
    both = _mm(u, p["wq"]).reshape(L, heads, 2 * hd)
    gate = both[..., hd:]
    q = apply_rotary(zrms(both[..., :hd], p["q_norm"], eps), pos,
                     cfg["rope_theta"], rot)
    k = apply_rotary(zrms(_mm(u, p["wk"]).reshape(L, kv, hd), p["k_norm"],
                          eps), pos, cfg["rope_theta"], rot)
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
    o = o * jax.nn.sigmoid(gate)
    return _mm(o.reshape(L, heads * hd), p["wo"])


def gated_mlp(u, w1, w3, w2):
    return _mm(jax.nn.silu(_mm(u, w1)) * _mm(u, w3), w2)


def routing(p, cfg, u):
    """(expert ids [L, k], weights [L, k]) over ALL the experts: softmax
    scores, the k largest, renormalised."""
    scores = jax.nn.softmax(u.astype(jnp.float32) @ p["router"], axis=-1)
    top, ids = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return ids, top


def routed(p, cfg, share, u):
    """The held experts' part of the routed sum; nothing else."""
    ids, weights = routing(p, cfg, u)
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


def shared(p, u):
    """The shared expert under its sigmoid gate: what every rank computes
    alike."""
    return jax.nn.sigmoid(_mm(u, p["shared_gate"])) * gated_mlp(
        u, p["shared_w1"], p["shared_w3"], p["shared_w2"])


def moe(p, cfg, share, u):
    return routed(p, cfg, share, u) + shared(p, u)


def layer_forward(p, cfg, share, x, q_block=None):
    """One layer: the mixer by ``p["mixer"]``'s leaves, then the experts."""
    eps = cfg["rms_norm_eps"]
    mixer = p["mixer"]
    u = zrms(x, mixer["norm"], eps)
    h = x + (gated_delta_net(mixer, cfg, u) if "conv_w" in mixer
             else attention(mixer, cfg, u, q_block))
    m = p["moe"]
    return h + moe(m, cfg, share, zrms(h, m["norm"], eps))


def next_nll(h, norm, head, ids, length, cfg, share):
    """Sum over the first ``length - 1`` positions of the cross-entropy of
    position i's logits against id i + 1."""
    logits = _mm(zrms(h, norm, cfg["rms_norm_eps"]), head)
    logp = jax.nn.log_softmax(logits[:-1].astype(jnp.float32), axis=-1)
    labels = ids[1:] - share["vocab_offset"]
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(jnp.arange(ids.shape[0] - 1) < length - 1,
                             nll, 0.0))


def document_pieces(cfg, share, q_block=None):
    """The pieces a document goes through, each a compiled function of
    arrays alone: layers of one shape share ONE program, forward and
    backward, whatever the weights or the ids are.  Each is under
    ``jax.checkpoint``: it keeps only its inputs for the backward pass and
    computes its forward again there."""
    return {
        "layer": jax.jit(jax.checkpoint(
            lambda p, x: layer_forward(p, cfg, share, x, q_block))),
        "next": jax.jit(jax.checkpoint(
            lambda h, norm, head, ids, n: next_nll(
                h, norm, head, ids, n, cfg, share))),
    }


def document_nll(params, cfg, share, ids, length, pieces):
    """Sum over the first ``length - 1`` positions of the next-token
    cross-entropy.  ``ids`` may be padded past ``length``: every mixer is
    causal, so the padding stays out of every counted position."""
    x = params["embed"][ids - share["vocab_offset"]]
    for i in range(cfg["num_hidden_layers"]):
        x = pieces["layer"](params[f"layer_{i}"], x)
    return pieces["next"](x, params["final_norm"], params["head"], ids,
                          length)


def loss_and_grads(params, cfg, share, documents, q_block=None, pad_to=None):
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
            nll = document_nll(p, cfg, share, ids, n, pieces)
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
