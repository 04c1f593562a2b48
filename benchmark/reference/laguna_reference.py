"""Plain reference of the Laguna layer stack: forward, loss and gradients.

``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
one document at a time, no kernels, no batching, no cache, and nothing of
``hydragnn_tpu``: plain dicts in, plain arrays out.  It follows the
published ``config.json`` (poolside/Laguna-S-2.1, ``model_type`` laguna);
where the config is silent the Qwen2-MoE family's forms are used and named
in ``ASSUMED``.  It takes the same share description the program takes
(experts held and their offset, the key/value heads held, the rows of the
vocabulary) and computes exactly that share: what the absent experts and
heads would add is left out, and the partial result goes on to the next
layer.  ``whole_share`` is the uncut model.

``params`` is a nested dict of arrays, named as the program's own tree:

    embed                                   [V, D]
    layer_<l>/attn/{norm, wq, wk, wv, wg, wo}
    layer_<l>/ffn/{norm, w1, w3, w2}                 dense layers
    layer_<l>/moe/{norm, router, experts_w1, experts_w3, experts_w2,
                   shared_w1, shared_w3, shared_w2}  expert layers
    final_norm                              [D]
    head                                    [D, V]

A copy of this file lives in the program's tree
(``hydragnn_tpu/models/laguna_reference.py``); tests/test_laguna.py holds
the two byte-identical.
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
    "silu as the feed-forward activation (hidden_act is not in the config)",
    "softmax router scores, no correction bias (Qwen2-MoE family)",
    "no gate on the shared expert",
    "the per-head attention gate reads the same normed input as q",
    "no query/key norm",
)


def whole_share(cfg):
    """The share that holds everything: the uncut model."""
    return {"num_experts_total": cfg["num_experts"], "expert_offset": 0,
            "kv_heads_total": cfg["num_key_value_heads"],
            "kv_head_offset": 0, "vocab_total": cfg["vocab_size"],
            "vocab_offset": 0}


def rotary_inv_freq(rope, head_dim):
    """(inverse frequencies [rot/2], factor on cos and sin, rotary dims) of
    one layer kind's ``rope_parameters`` entry; ``yarn`` blends the
    interpolated and the extrapolated frequencies as Hugging Face's
    ``_compute_yarn_parameters`` does."""
    rot = int(head_dim * rope.get("partial_rotary_factor", 1.0))
    base = float(rope["rope_theta"])
    pos_freqs = base ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if rope.get("rope_type", "default") == "default":
        return 1.0 / pos_freqs, 1.0, rot
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor = float(rope["factor"])
    orig = float(rope["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (rot * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(rope.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction_dim(rope.get("beta_slow", 1))), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extrapolation = 1.0 - ramp
    inv_freq = ((1.0 / (factor * pos_freqs)) * (1.0 - extrapolation)
                + (1.0 / pos_freqs) * extrapolation)
    scale = rope.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0
    return inv_freq, float(scale), rot


def apply_rotary(x, positions, rope, head_dim):
    """Rotate the first ``rot`` dims of ``x`` [L, heads, head_dim] by the
    position (Hugging Face's ``rotate_half`` pairing: dim i with i+rot/2)."""
    inv_freq, scale, rot = rotary_inv_freq(rope, head_dim)
    ang = (positions.astype(jnp.float32)[:, None]
           * jnp.asarray(inv_freq, jnp.float32)[None, :])
    cos = (jnp.cos(ang) * scale)[:, None, :]
    sin = (jnp.sin(ang) * scale)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def attention(p, cfg, layer, u, q_block=None):
    """Gated grouped-query attention of one document ``u`` [L, D] (the
    normed input) with the heads this share holds."""
    L, hd = u.shape[0], cfg["head_dim"]
    kind = cfg["layer_types"][layer]
    heads = cfg["num_attention_heads_per_layer"][layer]
    kv = cfg["num_key_value_heads"]
    rope = cfg["rope_parameters"][kind]
    pos = jnp.arange(L)
    q = apply_rotary(_mm(u, p["wq"]).reshape(L, heads, hd), pos, rope, hd)
    k = apply_rotary(_mm(u, p["wk"]).reshape(L, kv, hd), pos, rope, hd)
    v = _mm(u, p["wv"]).reshape(L, kv, hd)
    gate = jax.nn.sigmoid(_mm(u, p["wg"]))                   # [L, heads]
    k = jnp.repeat(k, heads // kv, axis=1)
    v = jnp.repeat(v, heads // kv, axis=1)
    window = cfg["sliding_window"] if kind == "sliding_attention" else None

    def rows(q_rows, pos_rows):
        dist = pos_rows[:, None] - pos[None, :]
        seen = dist >= 0
        if window is not None:
            seen = seen & (dist < window)
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
    return _mm((o * gate[:, :, None]).reshape(L, heads * hd), p["wo"])


def gated_mlp(u, w1, w3, w2):
    return _mm(jax.nn.silu(_mm(u, w1)) * _mm(u, w3), w2)


def routing(p, cfg, u):
    """(expert ids [L, k], weights [L, k]) over ALL the experts: softmax
    scores, the k largest, renormalised, times the routed scaling factor."""
    scores = jax.nn.softmax(u.astype(jnp.float32) @ p["router"], axis=-1)
    top, ids = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return ids, top * cfg.get("moe_routed_scaling_factor", 1.0)


def moe(p, cfg, share, u, shared=True):
    """The held experts' part of the routed sum, plus the shared expert
    (every chip computes it alike; ``shared=False`` leaves it out so that
    shares can be added up)."""
    ids, weights = routing(p, cfg, u)
    out = jnp.zeros_like(u)
    for e in range(p["experts_w1"].shape[0]):
        w = jnp.sum(jnp.where(ids == share["expert_offset"] + e, weights,
                              0.0), axis=-1)
        out = out + w[:, None] * gated_mlp(
            u, p["experts_w1"][e], p["experts_w3"][e], p["experts_w2"][e])
    if shared:
        out = out + gated_mlp(u, p["shared_w1"], p["shared_w3"],
                              p["shared_w2"])
    return out


def layer_forward(p, cfg, share, layer, x, q_block=None):
    eps = cfg["rms_norm_eps"]
    h = x + attention(p["attn"], cfg, layer,
                      rms_norm(x, p["attn"]["norm"], eps), q_block)
    if cfg["mlp_layer_types"][layer] == "dense":
        f = p["ffn"]
        return h + gated_mlp(rms_norm(h, f["norm"], eps),
                             f["w1"], f["w3"], f["w2"])
    m = p["moe"]
    return h + moe(m, cfg, share, rms_norm(h, m["norm"], eps))


def document_logits(params, cfg, share, ids, q_block=None):
    """Logits [L, V held] of one document's token ids [L]."""
    x = params["embed"][ids - share["vocab_offset"]]
    for layer in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda p, x, layer=layer: layer_forward(
                p, cfg, share, layer, x, q_block))(
                    params[f"layer_{layer}"], x)
    return _mm(rms_norm(x, params["final_norm"], cfg["rms_norm_eps"]),
               params["head"])


def document_nll(params, cfg, share, ids, length, q_block=None):
    """Sum over the first ``length - 1`` positions of the cross-entropy of
    position i's logits against position i+1's id.  ``ids`` may be padded
    past ``length``: causal attention keeps the padding out of every
    counted position."""
    logits = document_logits(params, cfg, share, ids, q_block)
    logp = jax.nn.log_softmax(logits[:-1].astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(
        logp, (ids[1:] - share["vocab_offset"])[:, None], axis=-1)[:, 0]
    return jnp.sum(jnp.where(jnp.arange(ids.shape[0] - 1) < length - 1,
                             nll, 0.0))


def loss_and_grads(params, cfg, share, documents, q_block=None,
                   pad_to=None):
    """Mean next-token cross-entropy over every position of every document
    that has a successor, and its gradient: one document at a time, the
    sums accumulated.  ``pad_to(L)`` may round a length up (zeros appended,
    masked out) so that few distinct shapes are compiled."""
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(jax.value_and_grad(
            lambda p, ids, n: document_nll(p, cfg, share, ids, n, q_block)))
        total, grads, count = 0.0, None, 0
        for doc in documents:
            doc = np.asarray(doc, np.int32)
            n = len(doc)
            if n < 2:
                continue
            if pad_to is not None:
                doc = np.concatenate(
                    [doc, np.full(pad_to(n) - n, doc[-1], np.int32)])
            nll, g = fn(params, jnp.asarray(doc), n)
            total += float(nll)
            count += n - 1
            grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        grads = jax.tree.map(lambda a: a / count, grads)
    return total / count, grads
