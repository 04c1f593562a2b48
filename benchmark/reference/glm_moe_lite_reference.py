"""Plain reference of the GLM-4.7-Flash layer stack: forward, both losses
and every gradient.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
one document at a time, no kernels, no batching, no cache, and nothing of
``hydragnn_tpu``: plain dicts in, plain arrays out.  It follows the
published ``config.json`` (zai-org/GLM-4.7-Flash, ``model_type``
glm4_moe_lite), whose key names are DeepSeek-V2/V3's: latent attention
(MLA) in its unabsorbed, training form; one leading dense layer, then
sigmoid top-k routing under a correction bias (``topk_method: noaux_tc``,
one group) with one shared expert; one multi-token-prediction module.
Where the config is silent the DeepSeek-V3 forms are used and named in
``ASSUMED``.  It takes the share description the program takes (experts
held and their offset, the rows of the vocabulary) and computes exactly
that share: what the absent experts would add is left out, and the partial
result goes on to the next layer.  ``whole_share`` is the uncut model.

The correction bias is an INPUT here (``biases``: layer name -> [E]): it is
state that no gradient moves, and its update rule is the program's
(models/glm_moe_lite.py) and the tests'.

``params`` is a nested dict of arrays, named as the program's own tree:

    embed                                   [V, D]
    layer_<l>/attn/{norm, wdq, q_norm, wuq, wdkv, kv_norm, wukv, wo}
    layer_<l>/ffn/{norm, w1, w3, w2}                 the dense layers
    layer_<l>/moe/{norm, router, experts_w1, experts_w3, experts_w2,
                   shared_w1, shared_w3, shared_w2}  the expert layers
    final_norm                              [D]
    head                                    [D, V]
    mtp/{enorm, hnorm, eh_proj, final_norm}, mtp/layer/{attn, moe}

A copy of this file lives in the program's tree
(``hydragnn_tpu/models/glm_moe_lite_reference.py``);
tests/test_glm_moe_lite.py holds the two byte-identical.
"""

from __future__ import annotations

import functools
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
    "rotary pairs dim i with i + 32 (rotate_half) over all 64 rotary dims; "
    "a fixed permutation of the rotary columns of Wuq and Wdkv gives the "
    "interleaved pairing, so with seeded weights it is the same model",
    "softmax scale 1 / sqrt(qk_nope_head_dim + qk_rope_head_dim), no "
    "mscale (rope_scaling is null)",
    "one shared expert of width n_shared_experts x moe_intermediate_size, "
    "no gate on it",
    "the correction bias steps by 0.001 x sign(mean load - load) after a "
    "train step (DeepSeek-V3's bias update speed; not in the config)",
    "multi-token prediction as DeepSeek-V3 writes it: "
    "[RMSNorm(Emb(t_{i+1})) | RMSNorm(h_i)] eh_proj, the embedding first "
    "(the released checkpoints' order), one more expert layer, its own "
    "final norm, the MAIN embedding and the MAIN head's matrix",
    "the second loss's weight is 0.3 (DeepSeek-V3's; not in the config), "
    "and the two weights are normalised to sum to one as this framework's "
    "multi-head loss does: loss = (L_next + 0.3 L_next_next) / 1.3",
    "no sequence-wise auxiliary balance loss",
)


def whole_share(cfg):
    """The share that holds everything: the uncut model."""
    return {"num_experts_total": cfg["n_routed_experts"], "expert_offset": 0,
            "vocab_total": cfg["vocab_size"], "vocab_offset": 0}


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


def attention(p, cfg, u, q_block=None):
    """Latent attention of one document ``u`` [L, D] (the normed input),
    unabsorbed: queries through a normed rank-``q_lora_rank`` bottleneck,
    keys and values rebuilt from ONE normed rank-``kv_lora_rank`` latent a
    token, one rotary key shared by every head."""
    L, heads, eps = u.shape[0], cfg["num_attention_heads"], cfg["rms_norm_eps"]
    nope, rope, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    rank = cfg["kv_lora_rank"]
    pos = jnp.arange(L)
    cq = rms_norm(_mm(u, p["wdq"]), p["q_norm"], eps)
    q = _mm(cq, p["wuq"]).reshape(L, heads, nope + rope)
    down = _mm(u, p["wdkv"])
    ckv = rms_norm(down[:, :rank], p["kv_norm"], eps)
    kv = _mm(ckv, p["wukv"]).reshape(L, heads, nope + dv)
    theta = cfg["rope_theta"]
    q = jnp.concatenate(
        [q[..., :nope], apply_rotary(q[..., nope:], pos, theta)], axis=-1)
    kr = apply_rotary(down[:, None, rank:], pos, theta)      # [L, 1, rope]
    k = jnp.concatenate(
        [kv[..., :nope], jnp.broadcast_to(kr, (L, heads, rope))], axis=-1)
    v = kv[..., nope:]

    def rows(q_rows, pos_rows):
        seen = pos_rows[:, None] - pos[None, :] >= 0
        s = (jnp.einsum("qhd,khd->hqk", _r(q_rows), _r(k))
             / math.sqrt(nope + rope))
        w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _r(w), _r(v))

    if q_block and L > q_block and L % q_block == 0:
        # the same rows, ``q_block`` at a time, so that the [heads, L, L]
        # scores of a long document never exist at once
        o = jax.lax.map(
            jax.checkpoint(lambda lo: rows(
                jax.lax.dynamic_slice_in_dim(q, lo, q_block),
                lo + jnp.arange(q_block))),
            jnp.arange(0, L, q_block)).reshape(L, heads, dv)
    else:
        o = rows(q, pos)
    return _mm(o.reshape(L, heads * dv), p["wo"])


def gated_mlp(u, w1, w3, w2):
    return _mm(jax.nn.silu(_mm(u, w1)) * _mm(u, w3), w2)


def routing(p, cfg, u, bias):
    """(expert ids [L, k], weights [L, k]) over ALL the experts: sigmoid
    scores, the k largest of ``score + bias`` selected (one group), the
    weights the selected experts' unbiased scores, renormalised, times the
    routed scaling factor."""
    scores = jax.nn.sigmoid(u.astype(jnp.float32) @ p["router"])
    _, ids = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, ids, axis=-1)
    if cfg.get("norm_topk_prob", True):
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    return ids, top * cfg.get("routed_scaling_factor", 1.0)


def moe(p, cfg, share, u, bias, shared=True):
    """The held experts' part of the routed sum, plus the shared expert
    (every chip computes it alike; ``shared=False`` leaves it out so that
    shares can be added up)."""
    ids, weights = routing(p, cfg, u, bias)
    held = share["expert_offset"] + jnp.arange(p["experts_w1"].shape[0])
    # [L, held]: the weight a token gives each held expert, 0 where it did
    # not select it; every held expert computes every token
    w = jnp.sum(jnp.where(ids[:, :, None] == held, weights[:, :, None], 0.0),
                axis=1)
    hidden = (
        jax.nn.silu(jnp.einsum("ld,edf->elf", _r(u), _r(p["experts_w1"])))
        * jnp.einsum("ld,edf->elf", _r(u), _r(p["experts_w3"])))
    out = jnp.einsum("le,eld->ld", w, jnp.einsum(
        "elf,efd->eld", _r(hidden), _r(p["experts_w2"])))
    if shared:
        out = out + gated_mlp(u, p["shared_w1"], p["shared_w3"],
                              p["shared_w2"])
    return out


def layer_forward(p, cfg, share, x, bias=None, q_block=None):
    """One layer: dense where ``p`` has ``ffn``, else experts under
    ``bias``."""
    eps = cfg["rms_norm_eps"]
    h = x + attention(p["attn"], cfg, rms_norm(x, p["attn"]["norm"], eps),
                      q_block)
    if "ffn" in p:
        f = p["ffn"]
        return h + gated_mlp(rms_norm(h, f["norm"], eps),
                             f["w1"], f["w3"], f["w2"])
    m = p["moe"]
    return h + moe(m, cfg, share, rms_norm(h, m["norm"], eps), bias)


def successor_nll(h, norm, head, ids, length, cfg, share, ahead):
    """Sum, over the positions of the first ``length`` that have an
    ``ahead``-th successor among them, of the cross-entropy of position i's
    logits (``h`` normed, times ``head``) against id i + ``ahead``."""
    L = ids.shape[0]
    logits = _mm(rms_norm(h, norm, cfg["rms_norm_eps"]), head)
    logp = jax.nn.log_softmax(logits[:-ahead].astype(jnp.float32), axis=-1)
    labels = ids[ahead:] - share["vocab_offset"]
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    counted = jnp.arange(L - ahead) < length - ahead
    return jnp.sum(jnp.where(counted, nll, 0.0))


def module_input(m, embed, h, ids, cfg, share):
    """What the multi-token-prediction module's layer reads: position i
    gets h_i and the embedding of id i+1.  The last position has no next id
    and is given its own (counted nowhere, and causal attention lets no
    other position read it)."""
    eps = cfg["rms_norm_eps"]
    after = jnp.concatenate([ids[1:], ids[-1:]]) - share["vocab_offset"]
    return _mm(jnp.concatenate(
        [rms_norm(embed[after], m["enorm"], eps),
         rms_norm(h, m["hnorm"], eps)], axis=-1), m["eh_proj"])


def document_pieces(cfg, share, q_block=None):
    """The pieces a document goes through, each a compiled function of
    arrays alone: layers of one shape share ONE program, forward and
    backward, whatever the bias, the weights or the ids are (a whole
    document as one program was 150 MB of executable at the benchmark's
    size, of which five sixths were the same expert layer again).  Each is
    under ``jax.checkpoint``: it keeps only its inputs for the backward
    pass and computes its forward again there."""
    fixed = {"cfg": cfg, "share": share}
    return {name: jax.jit(jax.checkpoint(f)) for name, f in {
        "layer": lambda p, x, b: layer_forward(p, cfg, share, x, b, q_block),
        "next": functools.partial(successor_nll, ahead=1, **fixed),
        "next_next": functools.partial(successor_nll, ahead=2, **fixed),
        "module_input": functools.partial(module_input, **fixed),
    }.items()}


def document_nlls(params, cfg, share, biases, ids, length, pieces):
    """(sum over the first ``length - 1`` positions of the cross-entropy of
    position i's logits against id i+1, sum over the first ``length - 2``
    positions of the multi-token-prediction module's against id i+2).
    ``ids`` may be padded past ``length``: causal attention keeps the
    padding out of every counted position."""
    x = params["embed"][ids - share["vocab_offset"]]
    for i in range(cfg["num_hidden_layers"]):
        name = f"layer_{i}"
        x = pieces["layer"](params[name], x, biases.get(name))
    # x: the last main layer's output, before the final norm
    nxt = pieces["next"](x, params["final_norm"], params["head"], ids, length)
    # one prediction depth more: one more expert layer over the module's
    # input, its own final norm, the MAIN head's matrix
    m = params["mtp"]
    x = pieces["module_input"](m, params["embed"], x, ids)
    x = pieces["layer"](m["layer"], x, biases["mtp"])
    nxt2 = pieces["next_next"](x, m["final_norm"], params["head"], ids,
                               length)
    return nxt, nxt2


def loss_and_grads(params, cfg, share, biases, documents, weight_next_next,
                   q_block=None, pad_to=None):
    """(loss, (L_next, L_next_next), gradient of loss): ``L_next`` the mean
    next-token cross-entropy over every position of every document that
    has a successor, ``L_next_next`` the module's over every position with
    a second successor, ``loss = (L_next + w L_next_next) / (1 + w)``.  One
    document at a time, the sums accumulated.  ``pad_to(L)`` may round a
    length up (the last id appended, masked out) so that few distinct
    shapes are compiled.  A document is differentiated piece by piece
    (``document_pieces``)."""
    with jax.default_matmul_precision("highest"):
        docs = [np.asarray(d, np.int32) for d in documents if len(d) >= 2]
        counts = (sum(len(d) - 1 for d in docs),
                  max(sum(len(d) - 2 for d in docs), 1))
        w = (1.0 / (1.0 + weight_next_next),
             weight_next_next / (1.0 + weight_next_next))
        scale = (w[0] / counts[0], w[1] / counts[1])
        pieces = document_pieces(cfg, share, q_block)

        def weighted(p, ids, n):
            nxt, nxt2 = document_nlls(p, cfg, share, biases, ids, n, pieces)
            return scale[0] * nxt + scale[1] * nxt2, (nxt, nxt2)

        # the running sum is donated: a document's gradient is added in place
        add = jax.jit(lambda acc, g: jax.tree.map(jnp.add, acc, g),
                      donate_argnums=(0,))
        sums, grads = [0.0, 0.0], jax.tree.map(jnp.zeros_like, params)
        for doc in docs:
            n = len(doc)
            if pad_to is not None:
                doc = np.concatenate(
                    [doc, np.full(pad_to(n) - n, doc[-1], np.int32)])
            (_, (a, b)), g = jax.value_and_grad(weighted, has_aux=True)(
                params, jnp.asarray(doc), n)
            grads = add(grads, g)
            sums[0] += float(a)
            sums[1] += float(b)
    each = (sums[0] / counts[0], sums[1] / counts[1])
    return w[0] * each[0] + w[1] * each[1], each, grads
