"""The routed-expert layer of one expert-parallel rank, dropless.

The layer is TOLD which experts it holds (parallel/share.py LayerShare).
It routes every node over ALL the experts (the router keeps its published
width), keeps the slots that fall on its own experts, sorts them by expert
and runs the gated feed-forward as grouped matrix products over the ragged
groups; the weighted outputs are added up per node.  What the other
ranks' experts would add is left out: on one chip there is no exchange.

No slot of a held expert is dropped.  The grouped path works on a static
number of rows, ``capacity`` (a multiple of the kernel's row tile, by
default four times the load a uniform router gives this rank: an untrained
router already sends one held expert three times the mean, and a rank that
trains alone draws load towards its own experts, the only ones whose
output the loss sees; twice was passed within 25 steps on the chip, PERF.md
section 6); when the held
slots of a step exceed it, that step takes the dense path instead (every
held expert applied to every node under its routing weights), which is
exact at any load and costs ``experts_held`` times the products.  The
choice is a ``lax.cond`` on the counted load, and ``stats["dense_steps"]``
counts it like ``fused_fallback`` (telemetry/logger.py step records).

Both row movements are gathers in both directions: the sorted order is a
partial permutation of the slots, so the transpose of "take row ``pos``"
is "take slot ``order``" and no scatter-add runs forward or backward.

Grouped product backends: ``gmm`` is JAX's megablox Pallas kernel
(``jax.experimental.pallas.ops.tpu.megablox``, forward gmm, backward gmm +
tgmm; its grid follows the counted rows, so rows past the load cost
nothing), the TPU path; ``ragged_dot`` is ``jax.lax.ragged_dot``, the CPU
path and the twin the tests hold the kernel to.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from hydragnn_tpu.utils.scope import phase

ROW_TILE = 512


def default_backend() -> str:
    return "gmm" if jax.default_backend() == "tpu" else "ragged_dot"


def default_capacity(num_nodes, top_k, experts_held, num_experts_total,
                     factor=4.0):
    """Rows of the grouped path: ``factor`` times the uniform router's
    load on this rank, at most every slot a node can send here, rounded up
    to the row tile."""
    uniform = num_nodes * top_k * experts_held / num_experts_total
    most = num_nodes * min(top_k, experts_held)
    rows = min(int(factor * uniform) + 1, most)
    return -(-rows // ROW_TILE) * ROW_TILE


def route(u, router_w, top_k, norm_topk=True, scale=1.0):
    """(expert ids [N, k], weights [N, k]) over all the experts: softmax
    scores in float32, the k largest, renormalised, times ``scale``.  The
    product runs at HIGHEST precision whatever the step's default is: a
    bf16 pass moves scores by 2^-9, enough to swap the 10th and 11th
    expert of a node, and a swapped expert is a different function."""
    logits = jnp.dot(u.astype(jnp.float32), router_w.astype(jnp.float32),
                     precision=lax.Precision.HIGHEST)
    top, ids = lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
    if norm_topk:
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    return ids, top * scale


def _gather_sum(src, pos, coef):
    """``y[n] = sum_k coef[n, k] * src[pos[n, k]]`` in float32, one gather
    per k so that no [N, k, D] array is made."""
    y = jnp.zeros((pos.shape[0], src.shape[1]), jnp.float32)
    for j in range(pos.shape[1]):
        y = y + coef[:, j, None] * jnp.take(
            src, pos[:, j], axis=0).astype(jnp.float32)
    return y


@jax.custom_vjp
def _dispatch(u, rows_node, rows_ok, pos, slot_ok):
    """Rows of ``u`` in sorted-slot order: [C, D]."""
    return jnp.take(u, rows_node, axis=0) * rows_ok[:, None].astype(u.dtype)


def _dispatch_fwd(u, rows_node, rows_ok, pos, slot_ok):
    return _dispatch(u, rows_node, rows_ok, pos, slot_ok), (pos, slot_ok)


def _dispatch_bwd(res, g):
    pos, slot_ok = res
    du = _gather_sum(g, pos, slot_ok.astype(jnp.float32))
    return du.astype(g.dtype), None, None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out, w, rows_node, rows_k, rows_ok, pos, slot_ok):
    """``y[n] = sum_k w[n, k] * out[pos[n, k]]`` over the held slots."""
    return _gather_sum(out, pos, jnp.where(slot_ok, w, 0.0))


def _combine_fwd(out, w, rows_node, rows_k, rows_ok, pos, slot_ok):
    y = _combine(out, w, rows_node, rows_k, rows_ok, pos, slot_ok)
    return y, (out, w, rows_node, rows_k, rows_ok, pos, slot_ok)


def _combine_bwd(res, dy):
    out, w, rows_node, rows_k, rows_ok, pos, slot_ok = res
    dy_rows = jnp.take(dy, rows_node, axis=0)                    # [C, D]
    w_rows = jnp.where(rows_ok, w[rows_node, rows_k], 0.0)       # [C]
    dout = (dy_rows * w_rows[:, None]).astype(out.dtype)
    dw_rows = jnp.sum(dy_rows * out.astype(jnp.float32), axis=-1)
    dw = jnp.where(slot_ok, jnp.take(dw_rows, pos, axis=0), 0.0)
    return dout, dw.astype(w.dtype), None, None, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def _grouped(x, w, group_sizes, backend, interpret):
    """``x[rows of group e] @ w[e]`` -> float32 [C, n]; rows past the
    groups give zeros."""
    with phase("moe.gmm"):
        if backend == "ragged_dot":
            return lax.ragged_dot(x, w, group_sizes,
                                  preferred_element_type=jnp.float32)
        from jax.experimental.pallas.ops.tpu.megablox import ops as mb

        # one more group holds the rows past the load: the kernel skips it
        # (rhs has no such expert) and zeroes its rows
        rest = x.shape[0] - jnp.sum(group_sizes)
        sizes = jnp.concatenate([group_sizes, rest[None]]).astype(jnp.int32)
        # tiles of (rows, contraction, columns); the backward tgmm holds a
        # float32 [contraction, columns] accumulator beside its operands,
        # and 512 x 1024 is what the 16 MiB of scoped VMEM leave room for
        tn = 512 if x.dtype == jnp.float32 else 1024
        return mb.gmm(x, w, sizes, jnp.float32,
                      (ROW_TILE, min(512, x.shape[1]), min(tn, w.shape[2])),
                      jnp.zeros((), jnp.int32), None, False, interpret)


def _silu_mul(h1, h3, dtype):
    return (jax.nn.silu(h1) * h3).astype(dtype)


def _grouped_path(u, ids, weights, local, held, w1, w3, w2, capacity,
                  backend, interpret):
    n, k = ids.shape
    held_n = w1.shape[0]
    key = jnp.where(held, local, held_n).reshape(-1)
    order = jnp.argsort(key, stable=True)              # sorted pos -> slot
    pos = jnp.argsort(order).reshape(n, k)             # slot -> sorted pos
    group_sizes = jnp.bincount(key, length=held_n + 1)[:held_n].astype(
        jnp.int32)
    # (a tiny batch has fewer slots than one row tile: pad the order)
    rows = jnp.pad(order, (0, max(0, capacity - n * k)))[:capacity]
    rows_ok = jnp.arange(capacity) < jnp.sum(group_sizes)
    rows_node, rows_k = rows // k, rows % k
    slot_ok = held & (pos < capacity)
    pos = jnp.minimum(pos, capacity - 1)
    x = _dispatch(u, rows_node, rows_ok, pos, slot_ok)
    h = _silu_mul(_grouped(x, w1, group_sizes, backend, interpret),
                  _grouped(x, w3, group_sizes, backend, interpret), u.dtype)
    out = _grouped(h, w2, group_sizes, backend, interpret)
    return _combine(out, weights, rows_node, rows_k, rows_ok, pos, slot_ok)


def _dense_path(u, ids, weights, local, held, w1, w3, w2):
    """Every held expert on every node under its routing weight.  One
    expert at a time, recomputed in the backward pass: nothing of size
    [experts, N, ...] is kept (the sum's own carry needs no residual)."""
    @jax.checkpoint
    def expert(e):
        we = jnp.sum(jnp.where(held & (local == e), weights, 0.0), axis=-1)
        h = _silu_mul(
            jnp.dot(u, w1[e], preferred_element_type=jnp.float32),
            jnp.dot(u, w3[e], preferred_element_type=jnp.float32), u.dtype)
        return we[:, None] * jnp.dot(
            h, w2[e], preferred_element_type=jnp.float32)

    y, _ = lax.scan(lambda y, e: (y + expert(e), None),
                    jnp.zeros(u.shape, jnp.float32),
                    jnp.arange(w1.shape[0]))
    return y


def routed_experts(u, router_w, w1, w3, w2, share, *, top_k, node_mask=None,
                   norm_topk=True, scale=1.0, compute_dtype=jnp.float32,
                   capacity=None, backend=None, interpret=False):
    """The held experts' part of the routed sum for nodes ``u`` [N, D].

    ``w1``/``w3`` [held, D, F], ``w2`` [held, F, D], ``router_w`` [D, E].
    Padding nodes (``node_mask`` 0) are routed nowhere: they all carry the
    same input, and would land on one expert together.  Returns (float32
    [N, D], stats): ``slots_held`` routed to held experts, ``slots_all``
    of the real nodes, ``load_max_over_mean`` over the held experts,
    ``dense_steps`` (1.0 when the dense path ran)."""
    backend = backend or default_backend()
    n = u.shape[0]
    with phase("moe.route"):
        ids, weights = route(u, router_w, top_k, norm_topk, scale)
        local, held = share.local_expert(ids)
        real = (jnp.ones((n,), bool) if node_mask is None
                else node_mask > 0)
        held = held & real[:, None]
        loads = jnp.sum(
            (local[..., None] == jnp.arange(share.experts_held)) &
            held[..., None], axis=(0, 1)).astype(jnp.float32)
        load = jnp.sum(loads)
    if capacity is None:
        capacity = default_capacity(n, top_k, share.experts_held,
                                    share.num_experts_total)
    uc = u.astype(compute_dtype)
    w1, w3, w2 = (w.astype(compute_dtype) for w in (w1, w3, w2))
    with phase("moe.experts"):
        grouped = functools.partial(
            _grouped_path, capacity=capacity, backend=backend,
            interpret=interpret)
        fits = load <= capacity
        y = lax.cond(fits, grouped, _dense_path,
                     uc, ids, weights, local, held, w1, w3, w2)
    stats = {
        "slots_held": load,
        "slots_all": jnp.sum(real).astype(jnp.float32) * top_k,
        "load_max_over_mean": jnp.max(loads) / jnp.maximum(
            load / share.experts_held, 1.0),
        "dense_steps": 1.0 - fits.astype(jnp.float32),
    }
    return y, stats
