"""The routed-expert layer of one expert-parallel rank, dropless.

The layer is TOLD which experts it holds (parallel/share.py LayerShare).
It routes every node over ALL the experts (the router keeps its published
width; ``route``: softmax scores and their k largest, or sigmoid scores
selected under a correction bias that carries no gradient; the selected
scores read by mask, the logits and the ids named so that a checkpoint
round the layer keeps them: ``KEEP_ROUTE``), keeps the slots that fall on
its own experts, sorts them by expert and runs the gated feed-forward as
grouped matrix products over the ragged groups; the weighted outputs are
added up per node.  What the other ranks' experts
would add is left out: on one chip there is no exchange.  The rows that
are dispatched need not be the rows the router reads (``rows=``: a latent
expert space), and the expert's form is an argument (``expert=``: the
gated three-matrix SiLU unit, or two matrices with ``relu(.)^2`` between):
models/nemotron_h.py.

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

Every row movement works on the rows this rank holds.  The held slots
(3 % of N x k in the benchmark's cell) are compacted in flat order
``n * k + j`` by one prefix sum, which leaves them sorted by node, and
sorted by expert over at most ``capacity`` keys: after the top-k nothing
gathers, sorts or scatters over N x k.  Two movements, each the other's
transpose (``jax.custom_vjp``): nodes -> rows (``x[r] = src[node[r]]``
for r < load, exact zeros past it: the dispatch forward, the combine
backward) and rows -> nodes (``y[n] = sum over n's held slots of c *
src[row]`` in float32: the combine forward, the dispatch backward).  On
the TPU both are Pallas kernels driven by scalar-prefetched indices and
the counted load; each fetches a row as the aligned HBM tile of 8 (16)
rows that holds it, since Mosaic slices HBM by whole tiles.  The first
grids over row tiles and starts no fetch at or past the load; the second
grids over node tiles, zeroes its block, walks its own range of held
slots and writes the block once: no read-modify-write in HBM.  Off the
TPU the same two movements are ``jnp.take`` and ``segment_sum``.

Grouped product backends: ``gmm`` is JAX's megablox Pallas kernel
(``jax.experimental.pallas.ops.tpu.megablox``, forward gmm, backward gmm +
tgmm; its grid follows the counted rows, so rows past the load cost
nothing), the TPU path; ``ragged_dot`` is ``jax.lax.ragged_dot``, the CPU
path and the twin the tests hold the kernel to.  ``backend`` chooses the
products and the row movement together.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from hydragnn_tpu.utils.scope import phase

ROW_TILE = 512          # rows of one grouped-product tile
GATHER_TILE = 128       # rows one step of the nodes -> rows kernel gathers
NODE_TILE = 128         # nodes one step of the rows -> nodes kernel sums
DMA_DEPTH = 16          # row fetches in flight in either kernel
SEARCH_BLOCK = 256      # slots of one block of the compacting search


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


# What a checkpoint that wraps an expert layer keeps of ``route`` (16.5 MB
# a layer at 7,440 nodes over 512 experts): with the logits and the ids kept,
# a recomputed forward runs neither the HIGHEST product nor ``top_k``.  The
# logits and not the scores: an activation's derivative reads its own
# output, which no name can reach, so naming the scores would leave the
# product in the recomputation.  ``KEEP_ROUTE`` is the policy of every such
# checkpoint (of the outermost one, where they nest).
ROUTE_LOGITS = "moe.route.logits"
ROUTE_IDS = "moe.route.ids"
KEEP_ROUTE = jax.checkpoint_policies.save_only_these_names(
    ROUTE_LOGITS, ROUTE_IDS)


def _selected(scores, ids):
    """``scores[n, ids[n, j]]``, [N, k], read by mask: a row's ids are
    distinct, so each sum over the experts has one non-zero term and IS the
    selected score, and its transpose is a masked sum over the slots with
    at most one term an element: both bit for bit what XLA's gather and
    scatter-add give (which cost 1.4 ms a call at 164 k keys, PERF.md
    section 6).  Slot by slot, [N, E] at a time, as ``_counts_all``: no
    [N, k, E] array exists.  The two barriers make ``scores`` (so its
    cotangent too) and the result arrays of their own, as they were round
    the gather.  Without them the TPU compiler sums a node's slots for the
    renormalisation in an order of its own and makes the cotangent inside
    the operand of the backward pass's two products: the last bit of the
    weights and of the router's gradients moves, and the step is 5 ms
    slower (PERF.md section 6)."""
    experts = jnp.arange(scores.shape[1], dtype=ids.dtype)
    scores = lax.optimization_barrier(scores)
    return lax.optimization_barrier(jnp.stack(
        [jnp.sum(jnp.where(ids[:, j:j + 1] == experts, scores, 0.0), axis=-1)
         for j in range(ids.shape[1])], axis=-1))


def route(u, router_w, top_k, norm_topk=True, scale=1.0, scoring="softmax",
          bias=None, norm_eps=None):
    """(expert ids [N, k], weights [N, k]) over all the experts, scores in
    float32.  ``scoring`` "softmax": softmax scores, the k largest,
    renormalised, times ``scale``.  ``scoring`` "sigmoid" (DeepSeek-V3's
    ``noaux_tc``, one group): sigmoid scores; with ``bias`` [E] the k
    largest of ``score + bias`` are SELECTED and the weights are the
    selected experts' UNbiased scores, renormalised (+ ``norm_eps``, None:
    1e-20, DeepSeek-V3's; models/lfm2_moe.py's family adds 1e-6), times
    ``scale``: the bias moves load and never a weight, and no gradient
    reaches it.  The product runs at HIGHEST precision whatever the step's
    default is: a bf16 pass moves scores by 2^-9, enough to swap the last
    selected expert of a node with the first one left out, and a swapped
    expert is a different function."""
    logits = checkpoint_name(
        jnp.dot(u.astype(jnp.float32), router_w.astype(jnp.float32),
                precision=lax.Precision.HIGHEST), ROUTE_LOGITS)
    if scoring == "softmax":
        scores, eps = jax.nn.softmax(logits, axis=-1), None
    elif scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        eps = 1e-20 if norm_eps is None else norm_eps
    else:
        raise ValueError(f"unknown router scoring {scoring!r}")
    # the selection carries no gradient; the values are read from ``scores``
    _, ids = lax.top_k(
        lax.stop_gradient(scores if bias is None else scores + bias), top_k)
    ids = checkpoint_name(ids, ROUTE_IDS)
    top = _selected(scores, ids)
    if norm_topk:
        total = jnp.sum(top, axis=-1, keepdims=True)
        top = top / (total if eps is None else total + eps)
    return ids, top * scale


class _Rows(NamedTuple):
    """The held slots of one step, twice: in ROW order (sorted by expert,
    node-major inside an expert: what the grouped products read) and in
    SLOT order (flat ``n * k + j``, so node-major: what a node's sum
    walks).  Every array is ``capacity`` long; entries at or past ``load``
    are dead (``slot_r`` is ``N * k`` there, ``row_t[t] == t``)."""
    node_r: jax.Array      # [C] node of row r
    slot_r: jax.Array      # [C] flat slot of row r
    row_t: jax.Array       # [C] row of the t-th held slot
    node_t: jax.Array      # [C] node of the t-th held slot
    tile_off: jax.Array    # [tiles + 1] first t of each node tile
    load: jax.Array        # [] held slots


def _compact(cum, payload, t):
    """(position, payload) of the (t + 1)-th counted position, ``cum`` the
    inclusive prefix sum of the counted mask.  A search in two levels, so
    that ONE gather runs (``len(t)`` rows of a block of ``cum`` and
    ``payload``): the block by comparing ``t`` with every block's last
    count, the place inside it by comparing with the fetched block.  A
    ``t`` at or past the count gives the last position."""
    blocks = -(-cum.shape[0] // SEARCH_BLOCK)
    fill = blocks * SEARCH_BLOCK - cum.shape[0]
    table = jnp.concatenate([
        jnp.pad(cum, (0, fill), mode="edge").reshape(blocks, SEARCH_BLOCK),
        jnp.pad(payload, (0, fill)).reshape(blocks, SEARCH_BLOCK)], axis=1)
    want = t[:, None] + 1
    block = jnp.minimum(jnp.sum(
        table[None, :, SEARCH_BLOCK - 1] < want, axis=1, dtype=jnp.int32),
        blocks - 1)
    fetched = jnp.take(table, block, axis=0)          # [len(t), 2 x block]
    place = jnp.minimum(jnp.sum(
        fetched[:, :SEARCH_BLOCK] < want, axis=1, dtype=jnp.int32),
        SEARCH_BLOCK - 1)
    here = jnp.arange(SEARCH_BLOCK, dtype=jnp.int32) == place[:, None]
    found = jnp.sum(jnp.where(here, fetched[:, SEARCH_BLOCK:], 0), axis=1)
    return jnp.minimum(block * SEARCH_BLOCK + place, cum.shape[0] - 1), found


def _held_rows(local, held, loads, capacity):
    """The index bookkeeping of the grouped path.  At N x k it is masks
    and ONE prefix sum; everything data-dependent (the search that
    compacts, the sort by expert, the inverse permutation) is ``capacity``
    long."""
    n, k = held.shape
    t = jnp.arange(capacity, dtype=jnp.int32)
    cum = jnp.cumsum(held.reshape(-1).astype(jnp.int32))
    load = jnp.sum(loads)
    slot_t, key_t = _compact(cum, local.reshape(-1).astype(jnp.int32), t)
    live = t < load
    # stable: the dead slots keep their places behind the live rows
    _, slot_r, t_r = lax.sort(
        (jnp.where(live, key_t, loads.shape[0]),
         jnp.where(live, slot_t, n * k), t), num_keys=1, is_stable=True)
    row_t = jnp.zeros((capacity,), jnp.int32).at[t_r].set(
        t, unique_indices=True)
    tiles = -(-n // NODE_TILE)
    stride = NODE_TILE * k
    tile_off = jnp.concatenate([
        jnp.zeros((1,), jnp.int32), cum[stride - 1::stride][:tiles - 1],
        load[None]])
    return _Rows(jnp.minimum(slot_r // k, n - 1), slot_r, row_t,
                 slot_t // k, tile_off, load)


def _sublanes(dtype):
    """Rows of one HBM tile: the unit a DMA may slice a 2-D array by."""
    return 8 * 4 // jnp.dtype(dtype).itemsize


def _walk(count, src, row_of, grp, sem, consume):
    """``consume(q, row)`` for q < count, ``row`` = ``src[row_of(q)]`` as
    float32 [1, D].  Mosaic slices HBM by whole tiles, so what moves is
    the aligned group of 8 (16 for 16-bit rows) rows that holds the row,
    one contiguous block; the row is picked out in VMEM.  ``len(grp)``
    fetches are in flight."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    depth, g = grp.shape[0], grp.shape[1]

    def copy(q, row):
        first = pl.multiple_of(row // g * g, g)
        return pltpu.make_async_copy(
            src.at[pl.ds(first, g)], grp.at[q % depth], sem.at[q % depth])

    def start(q, carry):
        copy(q, row_of(q)).start()
        return carry

    lax.fori_loop(0, jnp.minimum(depth, count), start, 0)

    def step(q, carry):
        row = row_of(q)
        copy(q, row).wait()
        sub = row % g
        if g == 8:
            picked = grp[q % depth, pl.ds(sub, 1), :].astype(jnp.float32)
        else:       # packed rows: no single-row load, select and add up
            block = grp[q % depth].astype(jnp.float32)
            mine = lax.broadcasted_iota(jnp.int32, (g, 1), 0) == sub
            picked = jnp.sum(jnp.where(mine, block, 0.0), axis=0,
                             keepdims=True)
        consume(q, picked)

        @pl.when(q + depth < count)
        def _next():
            start(q + depth, 0)
        return carry

    lax.fori_loop(0, count, step, 0)


def _nodes_to_rows_kernel(weighted, node_ref, load_ref, src, *rest):
    from jax.experimental import pallas as pl

    if weighted:
        coef_ref, other_ref, o_ref, dot_ref, grp, stage, sem = rest
    else:
        o_ref, grp, stage, sem = rest
    tr = o_ref.shape[0]
    base = pl.program_id(0) * tr
    live = jnp.clip(load_ref[0] - base, 0, tr)

    @pl.when(live == 0)
    def _dead():
        o_ref[...] = jnp.zeros_like(o_ref)
        if weighted:
            dot_ref[...] = jnp.zeros_like(dot_ref)

    @pl.when(live > 0)
    def _gather():
        def put(q, row):
            stage[pl.ds(q, 1), :] = row

        _walk(live, src, lambda q: node_ref[base + q], grp, sem, put)
        rows = lax.broadcasted_iota(jnp.int32, (tr, 1), 0)
        # a select, not a product: what the stage held before is anything
        got = jnp.where(rows < live, stage[...], 0.0)
        if weighted:
            dot_ref[...] = jnp.sum(
                got * other_ref[...].astype(jnp.float32), axis=1,
                keepdims=True)
            got = got * coef_ref[...]
        o_ref[...] = got.astype(o_ref.dtype)


def _rows_to_nodes_kernel(has_coef, row_ref, node_ref, off_ref, *rest):
    from jax.experimental import pallas as pl

    coef_ref, src, o_ref, grp, acc, sem = (
        rest if has_coef else (None,) + rest)
    tn = o_ref.shape[0]
    i = pl.program_id(0)
    t0 = off_ref[i]
    acc[...] = jnp.zeros_like(acc)

    def add(q, row):
        t = t0 + q
        if has_coef:
            row = row * coef_ref[row_ref[t]]
        at = pl.ds(node_ref[t] - i * tn, 1)
        acc[at, :] = acc[at, :] + row

    _walk(off_ref[i + 1] - t0, src, lambda q: row_ref[t0 + q], grp, sem, add)
    o_ref[...] = acc[...].astype(o_ref.dtype)


def _whole_groups(src):
    """``src`` with its rows padded to whole tiles (a no-op at the sizes a
    bucket has; a 20-node test batch is not one)."""
    g = _sublanes(src.dtype)
    return jnp.pad(src, ((0, -src.shape[0] % g), (0, 0)))


def _nodes_to_rows(src, rows, out_dtype, cfg, coef_r=None, other=None):
    """``x[r] = src[node_r[r]]`` for r < load, exact zeros past it: [C, D].
    The kernel's grid is the row tiles; a tile at or past the load starts
    no DMA and stores zeros, so nothing uninitialised reaches ``gmm`` /
    ``tgmm`` or a row-wise product.  With ``coef_r`` [C] and ``other``
    [C, D] (the combine's backward) it returns ``coef_r[r] * x[r]`` and the
    row-wise dots ``sum_d x[r, d] * other[r, d]``, float32 [C], made in
    VMEM over the live tiles: no pass over [C, D] in HBM besides the
    result's one write."""
    capacity = rows.node_r.shape[0]
    weighted = coef_r is not None
    if cfg.backend == "ragged_dot":
        live = jnp.arange(capacity) < rows.load
        x = jnp.where(live[:, None], jnp.take(src, rows.node_r, axis=0), 0)
        if not weighted:
            return x.astype(out_dtype)
        x = x.astype(jnp.float32)
        return ((x * coef_r[:, None]).astype(out_dtype),
                jnp.sum(x * other.astype(jnp.float32), axis=-1))
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    src = _whole_groups(src)
    d, g = src.shape[1], _sublanes(src.dtype)
    tile = pl.BlockSpec((GATHER_TILE, d), lambda i, *_: (i, 0))
    column = pl.BlockSpec((GATHER_TILE, 1), lambda i, *_: (i, 0))
    # a tile past the load asks for the last live tile again: no fetch
    live_tile = pl.BlockSpec((GATHER_TILE, d), lambda i, _node, load: (
        jnp.minimum(i, jnp.maximum(load[0] - 1, 0) // GATHER_TILE), 0))
    out = pl.pallas_call(
        functools.partial(_nodes_to_rows_kernel, weighted),
        out_shape=[jax.ShapeDtypeStruct((capacity, d), out_dtype)] + (
            [jax.ShapeDtypeStruct((capacity, 1), jnp.float32)]
            if weighted else []),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(capacity // GATHER_TILE,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] + (
                [column, live_tile] if weighted else []),
            out_specs=[tile] + ([column] if weighted else []),
            scratch_shapes=[pltpu.VMEM((DMA_DEPTH, g, d), src.dtype),
                            pltpu.VMEM((GATHER_TILE, d), jnp.float32),
                            pltpu.SemaphoreType.DMA((DMA_DEPTH,))]),
        interpret=cfg.interpret, name="moe_nodes_to_rows",
    )(rows.node_r, rows.load[None], src,
      *((coef_r.astype(jnp.float32)[:, None], other) if weighted else ()))
    return (out[0], out[1][:, 0]) if weighted else out[0]


def _rows_to_nodes(src, coef_r, rows, out_dtype, cfg):
    """``y[n] = sum over the held slots t of node n of coef_r[row_t[t]] *
    src[row_t[t]]``, added up in float32: [N, D].  The held slots in flat
    order are sorted by node, so the kernel's grid is the node tiles: each
    zeroes its block, walks its own range of slots (``tile_off``), fetches
    each row once and writes the block once."""
    n = cfg.nodes
    if cfg.backend == "ragged_dot":
        live = jnp.arange(rows.row_t.shape[0]) < rows.load
        picked = jnp.take(src, rows.row_t, axis=0).astype(jnp.float32)
        if coef_r is not None:
            picked = picked * jnp.take(coef_r, rows.row_t)[:, None]
        return jax.ops.segment_sum(
            jnp.where(live[:, None], picked, 0.0), rows.node_t,
            num_segments=n, indices_are_sorted=True).astype(out_dtype)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    src = _whole_groups(src)
    d, g = src.shape[1], _sublanes(src.dtype)
    coef = () if coef_r is None else (coef_r.astype(jnp.float32),)
    return pl.pallas_call(
        functools.partial(_rows_to_nodes_kernel, coef_r is not None),
        out_shape=jax.ShapeDtypeStruct((n, d), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + len(coef), grid=(-(-n // NODE_TILE),),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((NODE_TILE, d), lambda i, *_: (i, 0)),
            scratch_shapes=[pltpu.VMEM((DMA_DEPTH, g, d), src.dtype),
                            pltpu.VMEM((NODE_TILE, d), jnp.float32),
                            pltpu.SemaphoreType.DMA((DMA_DEPTH,))]),
        interpret=cfg.interpret, name="moe_rows_to_nodes",
    )(rows.row_t, rows.node_t, rows.tile_off, *coef, src)


class _Cfg(NamedTuple):
    backend: str
    interpret: bool
    nodes: int


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _dispatch(cfg, u, rows):
    """Rows of ``u`` in row order: [C, D], zeros past the load."""
    return _nodes_to_rows(u, rows, u.dtype, cfg)


def _dispatch_fwd(cfg, u, rows):
    return _dispatch(cfg, u, rows), rows


def _dispatch_bwd(cfg, rows, g):
    return _rows_to_nodes(g, None, rows, g.dtype, cfg), None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _combine(cfg, out, w_r, rows):
    """``y[n] = sum over the held slots of n of w * out[row]``, float32;
    ``w_r`` [C] is the routing weight of each row."""
    return _rows_to_nodes(out, w_r, rows, jnp.float32, cfg)


def _combine_fwd(cfg, out, w_r, rows):
    return _combine(cfg, out, w_r, rows), (out, w_r, rows)


def _combine_bwd(cfg, res, dy):
    out, w_r, rows = res
    dout, dw_r = _nodes_to_rows(dy, rows, out.dtype, cfg, w_r, out)
    return dout, dw_r.astype(w_r.dtype), None


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


def _relu2(h1, dtype):
    return jnp.square(jax.nn.relu(h1)).astype(dtype)


def _grouped_path(u, weights, local, held, loads, w1, w3, w2, capacity,
                  backend, interpret):
    """``w3`` None: the ungated two-matrix expert, ``relu(x w1)^2 w2``."""
    cfg = _Cfg(backend, interpret, u.shape[0])
    with phase("moe.rows"):
        rows = _held_rows(local, held, loads, capacity)
        # a dead row's slot is N * k: out of range, so it reads 0 and its
        # cotangent is dropped
        w_r = jnp.take(weights.reshape(-1), rows.slot_r, mode="fill",
                       fill_value=0.0)
        x = _dispatch(cfg, u, rows)
    if w3 is None:
        h = _relu2(_grouped(x, w1, loads, backend, interpret), u.dtype)
    else:
        h = _silu_mul(_grouped(x, w1, loads, backend, interpret),
                      _grouped(x, w3, loads, backend, interpret), u.dtype)
    out = _grouped(h, w2, loads, backend, interpret)
    with phase("moe.rows"):
        return _combine(cfg, out, w_r, rows)


def _dense_path(u, weights, local, held, loads, w1, w3, w2):
    """Every held expert on every node under its routing weight.  One
    expert at a time, recomputed in the backward pass: nothing of size
    [experts, N, ...] is kept (the sum's own carry needs no residual)."""
    @jax.checkpoint
    def expert(e):
        we = jnp.sum(jnp.where(held & (local == e), weights, 0.0), axis=-1)
        h1 = jnp.dot(u, w1[e], preferred_element_type=jnp.float32)
        h = (_relu2(h1, u.dtype) if w3 is None else _silu_mul(
            h1, jnp.dot(u, w3[e], preferred_element_type=jnp.float32),
            u.dtype))
        return we[:, None] * jnp.dot(
            h, w2[e], preferred_element_type=jnp.float32)

    y, _ = lax.scan(lambda y, e: (y + expert(e), None),
                    jnp.zeros((u.shape[0], w2.shape[2]), jnp.float32),
                    jnp.arange(w1.shape[0]))
    return y


def routed_experts(u, router_w, w1, w3, w2, share, *, top_k, node_mask=None,
                   norm_topk=True, scale=1.0, scoring="softmax", bias=None,
                   compute_dtype=jnp.float32, capacity=None, backend=None,
                   interpret=False, rows=None, expert="gated_silu",
                   norm_eps=None):
    """The held experts' part of the routed sum for nodes ``u`` [N, D].

    ``w1``/``w3`` [held, D, F], ``w2`` [held, F, D], ``router_w`` [D, E];
    ``scoring``, ``bias`` [E] and ``norm_eps`` as ``route`` takes them.
    ``rows`` [N, L]:
    what is dispatched to the experts where that is not what the router
    reads (a latent expert space, models/nemotron_h.py: ``w1`` [held, L,
    F], ``w2`` [held, F, L], the result [N, L]); None: ``u`` itself.
    ``expert``: ``"gated_silu"``, ``(silu(x w1) * (x w3)) w2``, or
    ``"relu2"``, ``relu(x w1)^2 w2`` with ``w3`` None.  Padding nodes
    (``node_mask`` 0) are routed nowhere: they all carry the same input,
    and would land on one expert together.  Returns (float32 [N, D],
    stats): ``slots_held`` routed to held experts, ``slots_all`` of the
    real nodes, ``load_max_over_mean`` over the held experts,
    ``dense_steps`` (1.0 when the dense path ran) and, with a ``bias``,
    ``counts_all`` [E]: the real nodes' slots on each of ALL the experts,
    what the bias's update reads."""
    backend = backend or default_backend()
    if (expert == "relu2") != (w3 is None) or expert not in (
            "gated_silu", "relu2"):
        raise ValueError(f"expert form {expert!r} with w3 "
                         f"{'absent' if w3 is None else 'given'}")
    n = u.shape[0]
    with phase("moe.route"):
        ids, weights = route(u, router_w, top_k, norm_topk, scale, scoring,
                             bias, norm_eps)
        local, held = share.local_expert(ids)
        real = (jnp.ones((n,), bool) if node_mask is None
                else node_mask > 0)
        held = held & real[:, None]
        loads = jnp.sum(
            (local[..., None] == jnp.arange(share.experts_held)) &
            held[..., None], axis=(0, 1), dtype=jnp.int32)
        load = jnp.sum(loads)
    if capacity is None:
        capacity = default_capacity(n, top_k, share.experts_held,
                                    share.num_experts_total)
    uc = (u if rows is None else rows).astype(compute_dtype)
    w1, w3, w2 = (None if w is None else w.astype(compute_dtype)
                  for w in (w1, w3, w2))
    with phase("moe.experts"):
        grouped = functools.partial(
            _grouped_path, capacity=capacity, backend=backend,
            interpret=interpret)
        fits = load <= capacity
        y = lax.cond(fits, grouped, _dense_path,
                     uc, weights, local, held, loads, w1, w3, w2)
    stats = {
        "slots_held": load.astype(jnp.float32),
        "slots_all": jnp.sum(real).astype(jnp.float32) * top_k,
        "load_max_over_mean": jnp.max(loads) / jnp.maximum(
            load / share.experts_held, 1.0),
        "dense_steps": 1.0 - fits.astype(jnp.float32),
    }
    if bias is not None:
        with phase("moe.bias"):
            stats["counts_all"] = _counts_all(
                ids, real, share.num_experts_total)
    return y, stats


COUNT_AT_ONCE = 1 << 24     # compares one [N, k, E] reduction may make


def _counts_all(ids, real, experts):
    """The real nodes' slots on each of ALL the experts, float32 [E].  One
    compare over [N, k, E] where that is small (4 of 64: 4.5 M at 17,512
    nodes); a wide router's (22 of 512: 141 M compares, which the TPU
    compiler materialises, 0.56 GB three times over) is counted slot by
    slot, [N, E] at a time."""
    n, k = ids.shape
    if n * k * experts <= COUNT_AT_ONCE:
        return jnp.sum((ids[..., None] == jnp.arange(experts)) &
                       real[:, None, None], axis=(0, 1), dtype=jnp.float32)

    def slot(j, counts):
        col = lax.dynamic_index_in_dim(ids, j, axis=1, keepdims=True)
        return counts + jnp.sum((col == jnp.arange(experts)) & real[:, None],
                                axis=0, dtype=jnp.float32)

    return lax.fori_loop(0, k, slot, jnp.zeros((experts,), jnp.float32))
