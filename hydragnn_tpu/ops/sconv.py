"""A double-gated short convolution over each graph's nodes.

A graph's nodes are contiguous on the packed node axis and in order
(graph/batch.py collate).  With ``z = b * x`` (elementwise), along the
nodes ``t`` of ONE graph and per channel ``c``::

    v_t[c] = sum_{j = 0 .. K-1} w[K - 1 - j, c] z_{t-j}[c]
    y_t    = c_t * v_t

``w`` [K, C] is laid out as ops/ssm.py ``graph_causal_conv`` documents it
(``w[K - 1]`` multiplies the node itself: torch's depthwise ``Conv1d``
weight ``[C, 1, K]`` under left padding ``K - 1``).  A tap that would read
another graph's node, a padding node, or before the axis reads zero;
padding rows give zero.  There is no bias and no activation function: the
two gates are the only non-linearity.

This stands BESIDE ``graph_causal_conv`` and not in it.  That function is a
float32 pre-filter of a mixer, ``taps`` passes of pad + compare + ``where``
over ids, with a bias and JAX's own backward pass, and its one caller's
traced program stays what it was.  Here the convolution is a layer's whole
operator between two matrix products, at the widest batch the benchmark
runs, so the boundary is decided ONCE a call, as ``reach``: how many
nodes back a node may read (its index inside its graph, at most ``K - 1``;
-1 on a padding row, which may not even read itself).  A tap of lag ``j``
is live where ``reach >= j``: one integer compare a tap on an [N, 1]
column, no id travels with the rows.

``graph_short_conv`` is a ``jax.custom_vjp``: the forward pass keeps its
three inputs (in the dtype they came in, bfloat16 in the benchmark's cell)
and nothing else; the backward pass computes ``z`` and ``v`` again and is
the transpose written out (``dz_s = sum_j w[K-1-j] [reach_{s+j} >= j]
dv_{s+j}``), so neither pass holds an [N, C] float32 array beyond what one
fused loop reads and writes.  Inside, everything is float32; the result
and the three gradients are written in the inputs' dtype.
``graph_short_conv_plain`` is the twin the tests hold it to: the same
mathematics through ``graph_causal_conv`` and JAX's own backward pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from hydragnn_tpu.ops.ssm import _real, graph_causal_conv, graph_starts


def tap_reach(node_gid, node_mask=None, taps=3):
    """[N] int32: how many nodes back a node may read: its index inside
    its graph, at most ``taps - 1``; -1 on a padding row."""
    n = node_gid.shape[0]
    real = _real(node_mask, n)
    idx = jnp.arange(n, dtype=jnp.int32)
    first = lax.cummax(jnp.where(graph_starts(node_gid, node_mask), idx, 0))
    return jnp.where(real, jnp.minimum(idx - first, taps - 1), -1)


def conv_counts(node_gid, node_mask=None, taps=3):
    """What ONE short convolution over this batch meets: (real rows, graph
    starts, taps of real rows that read zero because of a boundary: a
    graph's first node cuts ``taps - 1``, its second ``taps - 2``, ...),
    float32 scalars for the step records."""
    reach = tap_reach(node_gid, node_mask, taps)
    real = reach >= 0
    # a real row that may read nothing behind it is its graph's first
    return (jnp.sum(real).astype(jnp.float32),
            jnp.sum(reach == 0).astype(jnp.float32),
            jnp.sum(jnp.where(real, taps - 1 - reach, 0)
                    ).astype(jnp.float32))


def _back(a, lag):
    """``a[t - lag]`` at row t, zeros before the axis."""
    return jnp.pad(a, ((lag, 0), (0, 0)))[:a.shape[0]] if lag else a


def _ahead(a, lag):
    """``a[t + lag]`` at row t, zeros past the axis."""
    return jnp.pad(a, ((0, lag), (0, 0)))[lag:] if lag else a


def _conv(z, w, reach):
    taps = w.shape[0]
    return sum(jnp.where(reach[:, None] >= lag, _back(z, lag), 0.0)
               * w[taps - 1 - lag] for lag in range(taps))


@jax.custom_vjp
def _gated_conv(b, c, x, w, reach):
    z = b.astype(jnp.float32) * x.astype(jnp.float32)
    return (c.astype(jnp.float32) * _conv(z, w.astype(jnp.float32), reach)
            ).astype(b.dtype)


def _gated_conv_fwd(b, c, x, w, reach):
    return _gated_conv(b, c, x, w, reach), (b, c, x, w, reach)


def _gated_conv_bwd(res, dy):
    b, c, x, w, reach = res
    taps = w.shape[0]
    b32, c32, x32 = (a.astype(jnp.float32) for a in (b, c, x))
    w32, dy = w.astype(jnp.float32), dy.astype(jnp.float32)
    z = b32 * x32
    dv = dy * c32
    # a row's cotangent reaches the rows its live taps read
    live = [jnp.where(reach[:, None] >= lag, dv, 0.0) for lag in range(taps)]
    dz = sum(_ahead(live[lag], lag) * w32[taps - 1 - lag]
             for lag in range(taps))
    dw = jnp.stack([jnp.sum(live[lag] * _back(z, lag), axis=0)
                    for lag in reversed(range(taps))])
    return ((dz * x32).astype(b.dtype),
            (dy * _conv(z, w32, reach)).astype(c.dtype),
            (dz * b32).astype(x.dtype), dw.astype(w.dtype), None)


_gated_conv.defvjp(_gated_conv_fwd, _gated_conv_bwd)


def graph_short_conv(b, c, x, w, node_gid, node_mask=None):
    """``c * conv(b * x)`` over each graph's nodes: ``b``, ``c``, ``x``
    [N, C] of one dtype, ``w`` [K, C]; the result in that dtype."""
    return _gated_conv(b, c, x, w, tap_reach(node_gid, node_mask, w.shape[0]))


def graph_short_conv_plain(b, c, x, w, node_gid, node_mask=None):
    """The twin: float32 through ``graph_causal_conv`` (ids compared tap by
    tap), JAX's own backward pass; padding rows zeroed."""
    b32, c32, x32 = (a.astype(jnp.float32) for a in (b, c, x))
    v = graph_causal_conv(b32 * x32, w, jnp.zeros((w.shape[1],)), node_gid,
                          node_mask)
    real = _real(node_mask, b.shape[0])
    return jnp.where(real[:, None], c32 * v, 0.0)
