"""Attention over the nodes of each graph, the edge set implicit.

A document of 8192 tokens is a graph of 33 M causal edges; no edge list is
built.  Nodes of a graph are contiguous in a batch (graph/batch.py
collate), so node ``i`` sees node ``j`` iff they share ``node_gid`` and
``0 <= i - j`` (``< window`` on a banded layer): a static band over the
packed node axis, cut per graph by the ids.

Two backends, one contract (q [N, H, d], k and v [N, KV, d] -> [N, H, d];
``KV`` divides ``H``, query head ``a`` reads key/value head
``a // (H / KV)``; scores ``q . k / sqrt(d)``, softmax in float32).  Run
so far: grouped queries over ONE key/value head at ``d`` 128
(models/laguna.py), and as many key/value heads as query heads, 20 of
each, at ``d`` 256 (latent attention's rebuilt keys and values,
models/glm_moe_lite.py).

``splash``  JAX's segment-masked banded flash kernels
    (``jax.experimental.pallas.ops.tpu.splash_attention``: forward, dq and
    dkv kernels), the TPU path: ONE multi-head call where every query
    head has its own key/value head, else one multi-query call per
    key/value head.  Its block
    skipping follows the STATIC band only: a full-attention layer is
    banded to ``max_span`` (no graph is longer, so nothing visible is
    cut), and every block of that band is computed whatever the graphs'
    lengths are (PERF.md, Open questions).
``dense``   the masked [N, N] composition in ``jax.numpy``: the CPU path
    and the twin the tests hold the kernels to; quadratic in memory.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from hydragnn_tpu.utils.scope import phase

_BLOCK = 512      # splash tile edge, and the multiple the node axis pads to


def default_backend() -> str:
    return "splash" if jax.default_backend() == "tpu" else "dense"


def visible(node_gid, window):
    """The [N, N] mask of the contract above (tests, dense backend)."""
    idx = jnp.arange(node_gid.shape[0])
    dist = idx[:, None] - idx[None, :]
    seen = (node_gid[:, None] == node_gid[None, :]) & (dist >= 0)
    return seen & (dist < window) if window else seen


def _dense(q, k, v, node_gid, window):
    h, kv = q.shape[1], k.shape[1]
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k,
                   preferred_element_type=jnp.float32)
    s = jnp.where(visible(node_gid, window)[None],
                  s / math.sqrt(q.shape[-1]), -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("hqk,khd->qhd", w, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


@functools.lru_cache(maxsize=None)
def _splash_kernel(n, heads, band, interpret, multi_head=False):
    """The kernel for ``heads`` query heads on a node axis of ``n``, causal
    and banded to ``band`` back: multi-query over ONE key/value head, or
    (``multi_head``) each query head over a key/value head of its own."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    mask = sm.LocalMask((n, n), window_size=(band - 1, 0), offset=0)
    blocks = sk.BlockSizes(
        block_q=_BLOCK, block_kv=_BLOCK, block_kv_compute=_BLOCK,
        block_q_dkv=_BLOCK, block_kv_dkv=_BLOCK,
        block_kv_dkv_compute=_BLOCK, block_q_dq=_BLOCK, block_kv_dq=_BLOCK)
    # the mask tables are constants: made outside whatever trace calls us,
    # or the cache would hand one trace's tracers to the next
    with jax.ensure_compile_time_eval():
        make = sk.make_splash_mha if multi_head else sk.make_splash_mqa
        return make(
            sm.MultiHeadMask([mask] * heads), block_sizes=blocks,
            head_shards=1, q_seq_shards=1, interpret=interpret)


def _splash(q, k, v, node_gid, window, max_span, interpret):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    n, h, d = q.shape
    kv = k.shape[1]
    n_pad = -(-n // _BLOCK) * _BLOCK
    band = min(window or max_span or n_pad, n_pad)
    pad = ((0, n_pad - n), (0, 0), (0, 0))
    # the kernel takes the scale with q; rows past N form a graph of their
    # own, one id past the batch's padding graph
    q = jnp.pad(q * (1.0 / math.sqrt(d)), pad)
    k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    gid = jnp.pad(node_gid.astype(jnp.int32), (0, n_pad - n),
                  constant_values=jnp.iinfo(jnp.int32).max)
    seg = sk.SegmentIds(q=gid, kv=gid)
    if kv == h and h > 1:
        out = _splash_kernel(n_pad, h, band, bool(interpret), True)(
            q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1), seg)
        return out.swapaxes(0, 1)[:n].astype(q.dtype)
    kernel = _splash_kernel(n_pad, h // kv, band, bool(interpret))
    out = [kernel(q[:, a * (h // kv):(a + 1) * (h // kv)].swapaxes(0, 1),
                  k[:, a], v[:, a], seg) for a in range(kv)]
    return jnp.concatenate(out, axis=0).swapaxes(0, 1)[:n].astype(q.dtype)


def graph_attention(q, k, v, node_gid, *, window=None, max_span=None,
                    backend=None, interpret=False):
    """Causal attention inside each graph over the packed node axis.

    ``window``: nodes a node sees back, itself included (None = the whole
    graph so far); ``max_span``: an upper bound of a graph's node count,
    which bands a full layer's kernel (None = the node axis)."""
    backend = backend or default_backend()
    with phase("attn.core"):
        if backend == "dense":
            return _dense(q, k, v, node_gid, window)
        if backend == "splash":
            return _splash(q, k, v, node_gid, window, max_span, interpret)
    raise ValueError(f"unknown attention backend {backend!r}")
