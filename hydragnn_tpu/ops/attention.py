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

A padding node (``node_mask`` 0; graph/batch.py puts them all on ONE
trailing graph) is a graph of its own to both backends: it sees itself, so
its row stays finite, and nothing else.  Nothing reads a padding row.

``splash``  JAX's segment-masked banded flash kernels
    (``jax.experimental.pallas.ops.tpu.splash_attention``: forward, dq and
    dkv kernels), the TPU path: ONE multi-head call where every query
    head has its own key/value head, else one multi-query call per
    key/value head.  The kernel is built once per shape from the STATIC
    band (a full-attention layer is banded to ``max_span``: no graph is
    longer, so nothing visible is cut), which sizes its grid; which blocks
    of that band RUN follows the batch: ``_needed`` marks, from two
    strided reads of the ids, the blocks in which some graph has a
    visible pair, and each call hands the three kernels block tables with
    the others switched off (``_follow``).  A skipped block held only
    masked pairs, whose terms were exact zeros; it costs a grid step and
    no copy.
``dense``   the masked [N, N] composition in ``jax.numpy``: the CPU path
    and the twin the tests hold the kernels to; quadratic in memory.

What a checkpoint round an attention half may keep of the ``splash``
backend, by name (``KEEP_ATTN``, ``KEEP_ATTN_OUT`` below): the forward
kernel's result and log-sum-exp, which are all the two backward kernels
read of it, and the operands.  The ``dense`` backend names nothing.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from hydragnn_tpu.utils.scope import phase

_BLOCK = 512      # splash tile edge, and the multiple the node axis pads to

# What a checkpoint that wraps an attention half keeps of the splash
# backend.  ``ATTN_OUT`` is the forward kernel's result and its log-sum-exp
# ([heads, padded nodes] each, named inside the library's ``custom_vjp``):
# with them kept the backward pass runs the dq and dkv kernels and no
# forward kernel.  ``ATTN_Q`` / ``ATTN_K`` / ``ATTN_V`` are the operands as
# they arrive (the scale, pad and transpose behind them stay cheap
# recomputation): with them kept too, nothing upstream of the kernels is
# recomputed for the kernels' sake.  A half whose operands are few heads
# made by dear work (a grouped-query layer's normed, rotated q and k) keeps
# ``KEEP_ATTN``; one whose operands are many heads rebuilt from something
# narrow (latent attention's 20 heads of 256 from a 576-wide latent: 3.2 GB
# over six layers at 17,512 nodes) keeps ``KEEP_ATTN_OUT`` and rebuilds
# them.  ``kept_mb`` is the count under either.
ATTN_Q, ATTN_K, ATTN_V = "attn.core.q", "attn.core.k", "attn.core.v"
ATTN_OUT = "attn.core.out"
KEEP_ATTN = jax.checkpoint_policies.save_only_these_names(
    ATTN_Q, ATTN_K, ATTN_V, ATTN_OUT)
KEEP_ATTN_OUT = jax.checkpoint_policies.save_only_these_names(ATTN_OUT)


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


def _padded(n, window, max_span):
    """(the node axis padded to whole blocks, the band in nodes)."""
    n_pad = -(-n // _BLOCK) * _BLOCK
    return n_pad, min(window or max_span or n_pad, n_pad)


def _own_ids(node_gid, node_mask, n_pad):
    """``node_gid`` over the padded axis, every padding node and every row
    past N under an id of its own, past the real ones."""
    n = node_gid.shape[0]
    real = (jnp.ones((n,), bool) if node_mask is None else node_mask > 0)
    own = jnp.iinfo(jnp.int32).max - jnp.arange(n_pad, dtype=jnp.int32)
    return jnp.where(jnp.pad(real, (0, n_pad - n)),
                     jnp.pad(node_gid.astype(jnp.int32), (0, n_pad - n)),
                     own)


def _needed(gid, band):
    """(needed, in_band), both [blocks, blocks] over (query, key) blocks:
    the static band, and inside it the blocks where some graph has a
    visible pair.  Nodes of a graph are contiguous, so a graph reaches
    from block ``j`` into block ``i > j`` iff it holds ``j``'s last node
    and ``i``'s first, and that pair is also the nearest one; the
    diagonal always runs."""
    i = np.arange(gid.shape[0] // _BLOCK)[:, None]
    j = i.T
    in_band = (j <= i) & ((i - j - 1) * _BLOCK + 1 < band)
    spans = gid[::_BLOCK][:, None] == gid[_BLOCK - 1::_BLOCK][None, :]
    return in_band & ((i == j) | spans), in_band


def scheduled_blocks(node_gid, node_mask=None, *, window=None,
                     max_span=None):
    """(blocks one forward call of the splash backend runs on this batch,
    blocks of its static band): the non-zero entries of the forward table
    after and before ``_follow``, whichever backend runs."""
    n_pad, band = _padded(node_gid.shape[0], window, max_span)
    with phase("attn.core"):
        needed, in_band = _needed(_own_ids(node_gid, node_mask, n_pad), band)
        return jnp.sum(needed), int(in_band.sum())


@functools.lru_cache(maxsize=None)
def _name_primitive():
    """The primitive ``checkpoint_name`` binds: what a policy is asked."""
    return jax.make_jaxpr(lambda a: checkpoint_name(a, ""))(0.0).eqns[
        0].primitive


def named_mb(policy, named):
    """MB (1e6 bytes) of the arrays in ``named`` ({name: an array, a
    ``ShapeDtypeStruct`` or a byte count}) that a checkpoint under
    ``policy`` keeps, by asking ``policy`` for each name; 0 under no
    policy.  A number of the shapes alone."""
    if policy is None:
        return 0.0
    return sum(a if isinstance(a, int) else a.size * a.dtype.itemsize
               for name, a in named.items()
               if policy(_name_primitive(), name=name)) / 1e6


def kept_mb(q, k, v, policy, *, backend=None):
    """MB (1e6 bytes) that a checkpoint under ``policy`` keeps of ONE
    ``graph_attention`` call on these operands (``named_mb``): the forward
    kernel's result in ``q``'s dtype and its float32 log-sum-exp over the
    padded node axis (``ATTN_OUT``), and q, k and v as they arrive.  0
    under no policy, and on the ``dense`` backend, which names nothing."""
    if (backend or default_backend()) != "splash":
        return 0.0
    n, h, _ = q.shape
    rows = h * _padded(n, None, None)[0]
    return named_mb(policy, {
        ATTN_OUT: rows * (v.shape[-1] * q.dtype.itemsize + 4),
        ATTN_Q: q, ATTN_K: k, ATTN_V: v})


def _follow(info, needed, dkv):
    """``info`` (a kernel's static tables, [1, query blocks, positions], or
    for dkv [1, positions, key blocks]: the grid is shrunk to the band, and
    ``data_next`` says which key block (dkv: query block) a position
    means) with the blocks outside ``needed`` switched off.  A position
    switched off names the block the next running position of its row
    (dkv: column) reads, or past the last one the block it read, so it
    moves no data."""
    if dkv:     # the same walk with the two block axes exchanged
        needed = needed.T
    lay = (lambda a: a.swapaxes(1, 2)) if dkv else (lambda a: a)
    blk = lay(info.data_next).astype(jnp.int32)
    rows, width = blk.shape[1:]
    run = (lay(info.block_mask) != 0) & needed[jnp.arange(rows)[:, None], blk]
    pos = jnp.arange(width)
    ahead = jax.lax.cummin(jnp.where(run, pos, width), axis=2, reverse=True)
    behind = jax.lax.cummax(jnp.where(run, pos, 0), axis=2)
    data = jnp.take_along_axis(
        blk, jnp.where(ahead < width, ahead, behind), axis=2)
    return info._replace(
        data_next=lay(data).astype(info.data_next.dtype),
        block_mask=jnp.where(lay(run), info.block_mask, 0))


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
            head_shards=1, q_seq_shards=1, interpret=interpret,
            residual_checkpoint_name=ATTN_OUT)


def _splash(q, k, v, gid, band, interpret):
    """``gid``: ``_own_ids`` over the padded axis."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    n, h, d = q.shape
    kv = k.shape[1]
    n_pad = gid.shape[0]
    # named flat, [N, heads x d]: kept as [N, heads, d] the last two axes
    # (1 to 32 heads, a head of 64) would be padded to the chip's tiles
    q, k, v = (checkpoint_name(a.reshape(n, -1), name).reshape(a.shape)
               for a, name in ((q, ATTN_Q), (k, ATTN_K), (v, ATTN_V)))
    pad = ((0, n_pad - n), (0, 0), (0, 0))
    # the kernel takes the scale with q
    q = jnp.pad(q * (1.0 / math.sqrt(d)), pad)
    k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    seg = sk.SegmentIds(q=gid, kv=gid)
    multi_head = kv == h and h > 1
    static = _splash_kernel(n_pad, h if multi_head else h // kv, band,
                            bool(interpret), multi_head)
    needed, _ = _needed(gid, band)
    kernel = sk.SplashAttentionKernel(
        _follow(static.fwd_mask_info, needed, False),
        _follow(static.dq_mask_info, needed, False),
        _follow(static.dkv_mask_info, needed, True), **static.kwargs)
    if multi_head:
        out = kernel(q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
                     seg)
        return out.swapaxes(0, 1)[:n].astype(q.dtype)
    out = [kernel(q[:, a * (h // kv):(a + 1) * (h // kv)].swapaxes(0, 1),
                  k[:, a], v[:, a], seg) for a in range(kv)]
    return jnp.concatenate(out, axis=0).swapaxes(0, 1)[:n].astype(q.dtype)


def graph_attention(q, k, v, node_gid, node_mask=None, *, window=None,
                    max_span=None, backend=None, interpret=False):
    """Causal attention inside each graph over the packed node axis.

    ``node_mask``: the batch's, 0 on padding nodes (None = every node is
    real); ``window``: nodes a node sees back, itself included (None = the
    whole graph so far); ``max_span``: an upper bound of a graph's node
    count, which bands a full layer's kernel (None = the node axis)."""
    backend = backend or default_backend()
    n = q.shape[0]
    with phase("attn.core"):
        if backend == "dense":
            return _dense(q, k, v, _own_ids(node_gid, node_mask, n), window)
        if backend == "splash":
            n_pad, band = _padded(n, window, max_span)
            return _splash(q, k, v, _own_ids(node_gid, node_mask, n_pad),
                           band, interpret)
    raise ValueError(f"unknown attention backend {backend!r}")
