"""A selective state-space scan (Mamba-2's SSD) over each graph's nodes.

A graph's nodes are contiguous on the packed node axis and in order
(graph/batch.py collate).  Along the nodes ``t`` of ONE graph, per head
``h`` with its group's ``B`` and ``C``::

    S_t = exp(dt_t A_h) S_{t-1} + dt_t x_t B_t^T      S: [P, state]
    y_t = S_t C_t + D_h x_t

``S`` is zero before a graph's first node; padding nodes neither feed nor
read a state (their rows give ``D x``).  Nothing crosses a graph boundary.

``chunked`` (the TPU path) is the state-space-duality form at a chunk of
128 nodes: inside a chunk the recurrence is a masked matrix product,
``y = ((C B^T) * L * dt) x`` with ``L[t, s] = exp(cum_t - cum_s)`` for
``s <= t``, ``cum`` the running sum of ``dt A`` from the chunk's first node
(so no exponent is ever positive, and none larger than one chunk's sum);
each chunk's own contribution to the state is one more product; the states
are carried from chunk to chunk by a ``lax.scan`` over the chunks (one
multiply-add of a [heads, P, state] array a chunk, float32), and the
carried state enters a chunk's rows through a fourth product.  **Graph
boundaries are exact and use no infinite decay**: every factor that would
carry something from node ``s`` to node ``t`` is multiplied by ``same
graph(s, t)`` (ids compared, both nodes real), which for contiguous graphs
is the same as "no graph starts in (s, t]"; the chunk-to-chunk carry is
kept only where a chunk's last node and the previous chunk's last node
belong to one graph, and a row reads the carried state only where it
belongs to the graph of the previous chunk's last node.  A log-decay of
-inf on each graph's first node would do the same and poisons the
differences ``cum_t - cum_s`` with ``inf - inf``.

``sequential`` is the recurrence itself, one node a step of a
``lax.scan``: the CPU path and the twin the tests hold the chunked form to
(as ``ragged_dot`` is ``gmm``'s in ops/moe.py).

Precision: the products take operands in ``x``'s dtype (bfloat16 in the
benchmark's cell) and accumulate in float32; ``dt``, ``A``, the running
sums, every decay and the carried state are float32.  The backward pass is
JAX's own of either form; the layer that calls this is recomputed from its
input (models/nemotron_h.py), so the [chunks, heads, 128, 128] decay
matrices live for one layer's backward pass at a time.

``graph_causal_conv`` is the depthwise causal convolution in front of the
scan: a tap that would read another graph's node (or a padding node, or
before the axis) reads zero.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from hydragnn_tpu.utils.scope import phase


def default_backend() -> str:
    return "chunked" if jax.default_backend() == "tpu" else "sequential"


def _real(node_mask, n):
    return (jnp.ones((n,), bool) if node_mask is None else node_mask > 0)


def graph_starts(node_gid, node_mask=None):
    """[N] bool: a real node that is the first of its graph."""
    real = _real(node_mask, node_gid.shape[0])
    prev_gid = jnp.concatenate([node_gid[:1] - 1, node_gid[:-1]])
    prev_real = jnp.concatenate([jnp.zeros((1,), bool), real[:-1]])
    return real & ((node_gid != prev_gid) | ~prev_real)


def scan_counts(node_gid, node_mask=None, chunk=128):
    """What one scan over this batch walks: (chunks, chunks that hold no
    real node, graph starts), float32 scalars for the step records."""
    n = node_gid.shape[0]
    real = jnp.pad(_real(node_mask, n), (0, -n % chunk))
    per_chunk = jnp.sum(real.reshape(-1, chunk), axis=1)
    return (jnp.asarray(per_chunk.shape[0], jnp.float32),
            jnp.sum(per_chunk == 0).astype(jnp.float32),
            jnp.sum(graph_starts(node_gid, node_mask)).astype(jnp.float32))


def graph_causal_conv(x, w, b, node_gid, node_mask=None):
    """Depthwise causal convolution over each graph's nodes.

    ``x`` [N, C], ``w`` [K, C] (``w[K - 1]`` multiplies the node itself,
    ``w[0]`` the node K - 1 back: torch's ``Conv1d`` weight ``[C, 1, K]``
    under left padding K - 1), ``b`` [C] or None (a convolution without
    bias: models/qwen3_next.py).  Float32."""
    n, taps = x.shape[0], w.shape[0]
    real = _real(node_mask, n)
    x = x.astype(jnp.float32)
    out = None if b is None else jnp.broadcast_to(b.astype(jnp.float32),
                                                  x.shape)
    for lag in range(taps):
        same = real & jnp.pad(real, (lag, 0))[:n] & (
            jnp.pad(node_gid, (lag, 0), constant_values=-1)[:n] == node_gid)
        back = jnp.pad(x, ((lag, 0), (0, 0)))[:n]
        tap = jnp.where(same[:, None], back, 0.0) * w[taps - 1 - lag]
        out = tap if out is None else out + tap
    return out


def _sequential(x, dt, a, b, c, node_gid, real):
    n, heads, p = x.shape
    per_group = heads // b.shape[1]
    start = graph_starts(node_gid, real)
    x32 = x.astype(jnp.float32)
    # each head reads its group's B and C
    b32 = jnp.repeat(b.astype(jnp.float32), per_group, axis=1)
    c32 = jnp.repeat(c.astype(jnp.float32), per_group, axis=1)

    def step(s, row):
        xt, dtt, bt, ct, first, live = row
        s = jnp.where(first, 0.0, s)
        new = (jnp.exp(dtt * a)[:, None, None] * s
               + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        s = jnp.where(live, new, s)
        y = jnp.where(live, jnp.einsum("hps,hs->hp", s, ct), 0.0)
        return s, y

    _, y = lax.scan(step, jnp.zeros((heads, p, b.shape[2]), jnp.float32),
                    (x32, dt, b32, c32, start, real))
    return y


def _chunked(x, dt, a, b, c, node_gid, real, chunk):
    n, heads, p = x.shape
    groups, state = b.shape[1], b.shape[2]
    per_group = heads // groups
    dtype = x.dtype
    pad = -n % chunk
    nc = (n + pad) // chunk

    def chunks(v, fill=0):
        v = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1),
                    constant_values=fill)
        return v.reshape((nc, chunk) + v.shape[1:])

    # padding nodes: dt 0, so they neither decay nor feed a state
    dt = jnp.where(real[:, None], dt, 0.0)
    xc, bc, cc = chunks(x), chunks(b), chunks(c)
    dtc, gid, live = chunks(dt), chunks(node_gid, -1), chunks(real)
    # cum[c, t, h]: sum of dt A over the chunk's nodes up to t, <= 0
    cum = jnp.cumsum(dtc * a, axis=1)
    same = (gid[:, :, None] == gid[:, None, :]) & (
        live[:, :, None] & live[:, None, :])
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    # L[c, h, t, s] = exp(cum_t - cum_s) where s <= t in one graph, else 0
    cum_h = cum.transpose(0, 2, 1)                       # [nc, H, chunk]
    decay = jnp.exp(jnp.where(
        (same & causal)[:, None], cum_h[..., :, None] - cum_h[..., None, :],
        -jnp.inf))
    # C_t . B_s once a group, then every head of the group weighs it
    scores = jnp.einsum("ctgn,csgn->cgts", cc, bc,
                        preferred_element_type=jnp.float32)
    weights = (jnp.repeat(scores, per_group, axis=1) * decay
               * dtc.transpose(0, 2, 1)[:, :, None, :])
    y = jnp.einsum("chts,cshp->cthp", weights.astype(dtype), xc,
                   preferred_element_type=jnp.float32)

    # each chunk's own state at its last node: nodes of the last node's
    # graph, decayed from s to the chunk's end
    last_gid, last_live = gid[:, -1], live[:, -1]
    to_end = jnp.exp(jnp.where(
        ((gid == last_gid[:, None]) & live & last_live[:, None])[..., None],
        cum[:, -1:, :] - cum, -jnp.inf)) * dtc           # [nc, chunk, H]
    bh = jnp.repeat(bc, per_group, axis=2)               # [nc, chunk, H, n]
    own = jnp.einsum("cshp,cshn->chpn",
                     (xc.astype(jnp.float32) * to_end[..., None]
                      ).astype(dtype), bh,
                     preferred_element_type=jnp.float32)
    # the carry from chunk to chunk: kept where both last nodes are of one
    # graph (graphs are contiguous: no graph starts in between)
    prev_gid = jnp.concatenate([last_gid[:1] - 1, last_gid[:-1]])
    prev_live = jnp.concatenate([jnp.zeros((1,), bool), last_live[:-1]])
    keep = (last_gid == prev_gid) & last_live & prev_live
    carry = jnp.where(keep[:, None], jnp.exp(cum[:, -1, :]), 0.0)  # [nc, H]

    def step(s, row):
        k, o = row
        return k[:, None, None] * s + o, s

    _, entering = lax.scan(
        step, jnp.zeros((heads, p, state), jnp.float32), (carry, own))
    # a row reads the entering state where it is of the previous chunk's
    # last node's graph
    reads = jnp.where(
        ((gid == prev_gid[:, None]) & live & prev_live[:, None])[..., None],
        jnp.exp(cum), 0.0)                               # [nc, chunk, H]
    ch = jnp.repeat(cc, per_group, axis=2)               # [nc, chunk, H, n]
    y = y + reads[..., None] * jnp.einsum(
        "cthn,chpn->cthp", ch, entering.astype(dtype),
        preferred_element_type=jnp.float32)
    return y.reshape(nc * chunk, heads, p)[:n]


def graph_ssm(x, dt, A, B, C, D, node_gid, node_mask=None, *, chunk=128,
              backend=None):
    """The scan above for ``x`` [N, H, P], ``dt`` [N, H] (positive: after
    its softplus), ``A`` [H] (negative), ``B`` / ``C`` [N, G, state] (head
    ``h`` reads group ``h // (H / G)``), ``D`` [H]: float32 [N, H, P]."""
    backend = backend or default_backend()
    real = _real(node_mask, x.shape[0])
    dt, A = dt.astype(jnp.float32), A.astype(jnp.float32)
    with phase("ssm.scan"):
        if backend == "sequential":
            y = _sequential(x, dt, A, B, C, node_gid, real)
        elif backend == "chunked":
            y = _chunked(x, dt, A, B, C, node_gid, real, chunk)
        else:
            raise ValueError(f"unknown state-space backend {backend!r}")
        return y + D.astype(jnp.float32)[:, None] * x.astype(jnp.float32)
