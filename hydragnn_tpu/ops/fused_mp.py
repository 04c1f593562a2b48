"""Fused message-passing kernel: gather -> edge-multiply -> segment-sum in
one Pallas pass.

The CFConv-style core ``out[n] = sum_{e: recv[e]=n} x[send[e]] * w[e]`` is
the hot op of every conv stack.  XLA executes it as gather + multiply +
scatter; measured on the v5e the gather/scatter machinery dominates the
step's HBM traffic (cost model: 7.3 GB/step for the flagship SchNet, and
bf16-casting the features removes only ~3% of it), putting the step at the
bandwidth roofline.

This kernel exploits two invariants the collate layer guarantees
(graph/batch.py):

1. ``receivers`` are NONDECREASING (per-sample edge lists concatenated with
   node offsets), so each output node-block owns a contiguous edge range —
   scalar-prefetched searchsorted offsets steer the edge-block DMAs and no
   sort/scatter ever happens.  Read the other way: each edge block belongs
   to a contiguous run of node blocks, so a per-edge OUTPUT stream can be
   written from the same pass.
2. Edges are INTRA-GRAPH and graphs are stored contiguously, so the senders
   of a node block's edges lie within the adjacent node blocks — a 3-block
   x window (gathered as a block-local one-hot contraction on the MXU)
   replaces the global row gather, provided every graph fits in one node
   block (``max_nodes_per_graph <= _NODE_BLOCK``; callers must fall back to
   the XLA path otherwise).  Read the other way: the edges of node block i
   scatter to their senders only inside blocks i-hw..i+hw, so the
   transposed window one-hot replaces the global row scatter.

Padding edges (parked on node N-1 by collate with edge_mask 0) contribute
nothing: the caller's pre-masked ``w`` zeroes them, and out-of-window
one-hot rows are all-zero anyway.

The multiplier ``w`` has THREE sources, a static choice per call site; the
kernels, the schedule and the names (``gather_mul_seg_fwd`` / ``_bwd``) are
the same for all three:

  * an ARRAY ``w[E, F]`` streamed from HBM (:func:`gather_mul_segment_sum`
    — GAT's composed path, DimeNet's ``tri_window``, any caller whose
    multiplier already exists);
  * NOTHING: no multiplier operand at all, the messages are the gathered
    features and the ``[E]`` edge mask rides with the ids, inside the
    scatter's one-hot (:func:`gather_segment_sum` — the GIN / SAGE / MFC
    sums, ``poly_mp``'s sum-only backward);
  * a CHAIN (:func:`gather_chain_segment_sum`): per edge block an
    ``[BE, GPW]`` geometry tile and constant-mapped weight blocks, from
    which a pure-JAX ``chain(w_vals, geo, dt)`` makes ``w`` in VMEM, forward
    and backward — SchNet's filter network (ops/scf_mp.py packs it).  No
    ``[E, F]`` operand or cotangent exists in HBM: the geometry stream takes
    the ``w`` stream's place, the backward keeps ``dw`` in VMEM and pulls it
    back through the chain there (below).

The edge ids enter both kernels LANE-MAJOR (PR 29): ONE int32 operand
``[E_pad / 128 * 8, 128]`` (:func:`_pack_ids`) holds, per granule of 128
consecutive edges, one (8, 128) tile — sublane 0 the senders, 1 the
receivers, 2 the bits of the w-less form's f32 mask, the rest spare — so an
edge block's ids are 32 B an edge of HBM traffic (16 KiB a 512-edge step),
and every edge block either pass chooses (512 / 256 / 128) is a whole number
of granules of the same operand.  (Two ``[E_pad, 1]`` columns, the layout
before, are 128 lanes wide each as Mosaic operands: 512 KiB of the 768 a
step moved.)  In VMEM a sublane of the block is a ``[1, BE]`` row, and the
one-hots are built TRANSPOSED from it, ``[W * bn, BE]`` and ``[bn, BE]``
(:func:`_onehot_t`: an integer compare against a sublane iota): the gathers
are the transposed-lhs contraction, the scatters plain matmuls.  Measured on
the v5e this beat turning the id tile into columns in VMEM by 19 % forward
and 22 % on the gradient (PERF.md, PR 29).  The schedule and the XLA-side
selects read the flat ``[E_pad]`` receivers.

The grid is a DENSE CSR-style schedule: scalar-prefetched step tables map
each grid step to one populated (node-block, edge-block) pair, so no step
is a wasted DMA and — unlike a rectangular (block, k_max) grid bounded by a
declared max degree — ANY degree distribution is processed exactly (total
steps are unconditionally <= edge blocks + 2 * node blocks).

Backward: ONE pass (``gather_mul_seg_bwd``) on the same schedule, over the
edge list in the order collate ships it — nothing is sorted by sender and
XLA gathers, permutes or pads nothing E-sized.  Per (node block i, edge
block) step, with the two one-hots the forward builds (written here edges
by nodes; in VMEM they are the transposes, above):
``g_r = onehot_r @ g_i`` (block-local, zero rows for the edges of other
node blocks — the gate that counts every edge once), ``x_s = onehot_s @
x_window``, ``dw = x_s * g_r`` streamed out per edge (first accumulated
visit of an edge block overwrites, a boundary block's later visits add),
and ``P_i += onehot_s^T @ (w * g_r)`` — node block i's contributions to
``dx`` in window coordinates, ``[W * bn, F]`` per node block.  ``dx`` is
the overlap-add of the ``P_i`` in node space (slot k of block i lands on
block i - hw + k; slots off either end are dropped).  Edge blocks holding
only parked edges are never entered, so their ``dw`` rows are unwritten
memory, selected to exact zero.  The w-less op runs the same body without
the ``x`` window, the ``w`` block and the ``dw`` stream (its mask sits in the
window one-hot).  The chain form recomputes ``w`` from
its geometry tile, keeps ``dw`` in VMEM and pulls it back through the chain
inside the same step (``jax.vjp`` on the chain body): the weight
cotangents accumulate into constant-mapped f32 blocks zeroed at step 0 —
``g_r``'s zero rows gate ``dw`` and the pullback is linear in it, so every
edge feeds them exactly once — and a ``dgeo`` stream (``dw``'s first-visit
select) is written ONLY when the geometry is itself differentiated
(``custom_vjp(symbolic_zeros=True)`` tells the forward rule): with the
geometry from batch data the backward has no edge-sized output at all.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from hydragnn_tpu.ops.aggregate import _round_up
from hydragnn_tpu.ops.fused_block import (  # noqa: F401 — canonical home;
    _NODE_BLOCK, _dense_schedule)           # re-exported for back-compat
from hydragnn_tpu.ops.fused_block import _window_maps


_EDGE_BLOCK = 512   # edges per inner step


_LANES = 128       # edges per id granule: one (8, 128) int32 tile of HBM
_ID_ROWS = 8       # its sublanes, of which three are used:
_SEND, _RECV, _MASK = 0, 1, 2   # (2: the w-less form's f32 mask bits)


def _onehot_t(ids_ref, k, base, n, masked=False):
    """``[n, BE]`` f32: the one-hot of id set ``k`` of the edge block,
    relative to ``base``, TRANSPOSED — edges along the lanes, as the ids
    arrive.  Sublane ``k`` of the block's granules, side by side, is a
    ``[1, BE]`` row; comparing it (as integers) with a sublane iota
    broadcasts it down the sublanes, the cheap direction.  An id outside
    ``[base, base + n)`` (another block's edge, a parked edge) is an
    all-zero column.  ``masked`` puts the w-less form's edge-mask values
    where the ones are (0 / 1 from every caller, so exact in the MXU pass
    as the ones are)."""
    granules = ids_ref.shape[0] // _ID_ROWS

    def row(j):
        return jnp.concatenate(
            [ids_ref[_ID_ROWS * g + j:_ID_ROWS * g + j + 1, :]
             for g in range(granules)], axis=1)

    hit = (row(k) - base) == jax.lax.broadcasted_iota(
        jnp.int32, (n, granules * _LANES), 0)
    if not masked:
        return hit.astype(jnp.float32)
    return jnp.where(
        hit, jax.lax.bitcast_convert_type(row(_MASK), jnp.float32), 0.0)


def _gather(onehot_t, data):
    """``data[ids - base]`` -> ``[BE, F]``: the transposed-lhs contraction
    (zero rows where the one-hot has zero columns)."""
    return jax.lax.dot_general(
        onehot_t, data, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _scatter(onehot_t, m):
    """``sum_e onehot[e] * m[e]`` -> ``[n, F]``: a plain matmul."""
    return jax.lax.dot_general(
        onehot_t, m, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _multiplier(chain, src_refs, dt):
    """The edge block's multiplier from its source (module docstring): the
    ``w`` block as stored, or the chain evaluated in VMEM from its geometry
    block and constant weight blocks."""
    if chain is None:
        return src_refs[0][:].astype(jnp.float32)
    geo_ref, *w_refs = src_refs
    return chain(tuple(r[:] for r in w_refs), geo_ref[:], dt)


def _fwd_kernel(chain, window, si_ref, se_ref, av_ref, fi_ref, ids_ref,
                *rest):
    from jax.experimental import pallas as pl

    # the multiplier's source: ONE ref for an array ``w``, geo + weight
    # blocks for a chain, NONE without ``w`` (the messages are the gathered
    # features themselves, GIN/MFC-style, times the mask bits that ride
    # with the ids)
    src_refs = rest[:-(window + 1)]
    xwin_refs = rest[-(window + 1):-1]
    out_ref = rest[-1]

    s = pl.program_id(0)
    i = si_ref[s]

    @pl.when(fi_ref[s] == 1)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(av_ref[s] == 1)
    def _acc():
        bn = out_ref.shape[0]
        # window rows are blocks [i-hw .. i+hw]; at the boundaries the
        # clamped duplicate slots are unreachable because the base stays
        # (i-hw)*bn (negative at the low edge is fine — senders then map
        # into the later window rows, never the duplicated ones)
        hw = window // 2
        xcat = jnp.concatenate(
            [r[:] for r in xwin_refs], axis=0).astype(jnp.float32)
        msgs = _gather(_onehot_t(ids_ref, _SEND, (i - hw) * bn,
                                 window * bn), xcat)         # [BE, F]
        if src_refs:
            msgs = msgs * _multiplier(chain, src_refs, xwin_refs[0].dtype)
        out_ref[:] += _scatter(_onehot_t(
            ids_ref, _RECV, i * bn, bn, masked=not src_refs), msgs)


def _pack(x, w, senders, receivers, mask=None, edge_valid=None):
    """Zero-pad the operands to whole blocks — ``(x_p, w_p, ids_p,
    recv_f)``, the arrays both passes run on (the forward rule saves them
    as its residuals, so the backward pads nothing a second time).
    Without ``w`` there is no multiplier operand (``w_p`` is None): the
    ``[E]`` edge mask rides with the ids (:func:`_pack_ids`).

    Shape-padding edges are parked outside every block/window so they
    can't contribute even with nonzero data (their w rows are zero
    anyway).  MASK-padding edges (edge_valid == 0 — the batch's own
    padding, ~half the edge slots at flagship collate shapes) are parked
    the same way, so the dense schedule assigns their edge blocks to NO
    node block and never accumulates them.  Contract (callers): masked
    edges carry zero w/mask AND sort after all real edges (collate parks
    them on node N-1, the maximum id)."""
    has_w = w is not None
    n, f = x.shape
    e = w.shape[0] if has_w else senders.shape[0]
    n_pad = _round_up(n, _NODE_BLOCK)
    e_pad = _round_up(max(e, 1), _EDGE_BLOCK)
    f_pad = _round_up(max(f, 1), 128)

    # lax.pad, not zeros().at[].set(): the same one copy forward, and its
    # transpose is a slice — the scatter's would be an [E, F] gather
    x_p = jnp.pad(x, ((0, n_pad - n), (0, f_pad - f)))
    w_p = mask_bits = None
    if has_w:
        w_p = jnp.pad(w, ((0, e_pad - e), (0, f_pad - f)))
    else:
        mask_bits = jnp.ones((e,), jnp.float32) if mask is None else mask
    ids_p, recv_f = _pack_ids(senders, receivers, e_pad, n_pad, edge_valid,
                              mask_bits)
    return x_p, w_p, ids_p, recv_f


def _pack_ids(senders, receivers, e_pad, n_pad, edge_valid=None, mask=None):
    """``(ids_p, recv_f)``: the ids LANE-MAJOR, as the kernels read them,
    and the flat ``[E_pad]`` receivers the schedule and the XLA-side
    selects read.  Shape- and mask-padding edges are parked on ``n_pad``
    (:func:`_pack`).

    ``ids_p`` is ``[E_pad / 128 * 8, 128]`` int32: one (8, 128) tile — 4 KiB
    of HBM, 32 B an edge — per granule of 128 consecutive edges, sublane
    0 its senders, 1 its receivers, 2 the bits of the w-less form's f32
    ``mask`` (zero otherwise), the rest spare.  Every edge block either
    pass chooses (512 / 256 / 128) is a whole number of granules of the
    ONE operand.  (An ``[E_pad, 1]`` column, the layout until PR 29, is
    128 lanes wide in HBM: 512 B an edge and id set, 512 KiB of the 768
    a 512-edge step moved.)"""
    e = senders.shape[0]
    if edge_valid is not None:
        ev = edge_valid != 0
        senders = jnp.where(ev, senders, n_pad)
        receivers = jnp.where(ev, receivers, n_pad)

    def lanes(v, fill):
        return jnp.pad(v, (0, e_pad - e), constant_values=fill)

    send_f = lanes(senders.astype(jnp.int32), n_pad)
    recv_f = lanes(receivers.astype(jnp.int32), n_pad)
    rows = [send_f, recv_f]
    if mask is not None:
        rows.append(jax.lax.bitcast_convert_type(
            lanes(mask.astype(jnp.float32), 0.0), jnp.int32))
    tiles = jnp.concatenate(
        [r.reshape(-1, 1, _LANES) for r in rows], axis=1)
    tiles = jnp.pad(tiles, ((0, 0), (0, _ID_ROWS - len(rows)), (0, 0)))
    return tiles.reshape(-1, _LANES), recv_f


def _ids_spec(be, eix):
    """BlockSpec of one ``be``-edge block of :func:`_pack_ids`' operand."""
    from jax.experimental import pallas as pl

    return pl.BlockSpec((be // _LANES * _ID_ROWS, _LANES), eix)


def _chain_edge_block(f_pad, backward):
    """Edge block of the chain form.  Beside the array form's operands it
    holds the ``[F_pad, F_pad]`` weight block (and, backward, its f32
    gradient accumulator) and the chain's ``[BE, F_pad]`` f32 temporaries
    (~6 forward, ~15 with the pullback), so the block shrinks once F_pad
    passes 512: 256 forward, 128 backward (see :func:`_chain_vmem`)."""
    if f_pad <= 512:
        return _EDGE_BLOCK
    return _EDGE_BLOCK // 4 if backward else _EDGE_BLOCK // 2


def _chain_vmem(f_pad):
    """``compiler_params`` of the chain-form kernels.  At F_pad 128 both
    passes hold ~5 MB and the default 16 MiB scoped VMEM stands.  Wider,
    the stack comes within a megabyte of that limit or past it — a 1024
    bf16 backward asked for 16.84 MB on the v5e, and ambient ``highest``
    matmul precision adds the f32 dots' multi-pass scratch — so those
    kernels ask for 64 of the chip's 128 MiB instead (AOT-compiled,
    tools/mosaic_aot.py: 256 / 512 / 768 / 1024 in f32 and bf16, at
    default and ``highest``)."""
    from jax.experimental.pallas import tpu as pltpu

    if f_pad <= 128 or jax.default_backend() != "tpu":
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=64 * 1024 * 1024)}


def _fwd_call(window, x_p, w_p, ids_p, recv_f, chain=None, weights=()):
    """The forward pass on whole blocks.  With a ``chain`` the multiplier's
    slot ``w_p`` holds the ``[E_pad, GPW]`` geometry stream and
    ``weights`` its constant-mapped blocks; without ``w`` it is None."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    assert window % 2 == 1, "window must be odd"
    bn, be = _NODE_BLOCK, _EDGE_BLOCK
    n_pad, f_pad = x_p.shape
    if chain is not None:
        be = _chain_edge_block(f_pad, False)
    n_blocks, n_eblocks = n_pad // bn, recv_f.shape[0] // be
    step_i, step_eb, acc_valid, is_first, s_max = _dense_schedule(
        recv_f, n_blocks, bn, be, n_eblocks)
    eix, xoff, const, outx = _window_maps(n_blocks)
    src = [] if w_p is None else [w_p]

    hw = window // 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s_max,),
        in_specs=[_ids_spec(be, eix)]
        + [pl.BlockSpec((be, w.shape[1]), eix) for w in src]
        + [pl.BlockSpec(w.shape, const) for w in weights]
        + [pl.BlockSpec((bn, f_pad), xoff(o))
           for o in range(-hw, hw + 1)],
        out_specs=pl.BlockSpec((bn, f_pad), outx),
    )
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chain, window),
        out_shape=jax.ShapeDtypeStruct((n_pad, f_pad), jnp.float32),
        grid_spec=grid_spec,
        interpret=jax.default_backend() != "tpu",
        name="gather_mul_seg_fwd",
        **({} if chain is None else _chain_vmem(f_pad)),
    )(step_i, step_eb, acc_valid, is_first, ids_p, *src, *weights,
      *([x_p] * window))


# ---------------------------------------------------------------------------
# backward: ONE pass over the edge list in the order collate ships it
# ---------------------------------------------------------------------------


def _bwd_gathers(window, i, ids_ref, g_ref, xwin_refs, masked=False):
    """What every form of the backward step starts from: ``(g_r,
    onehot_st, x_s)`` — the cotangent at the receivers, the forward's
    window one-hot (transposed, :func:`_onehot_t`; ``masked`` as there),
    the features at the senders (None without windows)."""
    bn = g_ref.shape[0]
    # g[recv]: block-local, an edge of another node block gets an
    # all-zero row — it gates every product below, so a boundary edge
    # block contributes each edge exactly once (on its own block's
    # visit)
    g_r = _gather(_onehot_t(ids_ref, _RECV, i * bn, bn),
                  g_ref[:].astype(jnp.float32))              # [BE, F]
    # the forward's window one-hot (same base, same clamped slots)
    onehot_st = _onehot_t(ids_ref, _SEND, (i - window // 2) * bn,
                          window * bn, masked)
    if not xwin_refs:
        return g_r, onehot_st, None
    xcat = jnp.concatenate(
        [r[:] for r in xwin_refs], axis=0).astype(jnp.float32)
    return g_r, onehot_st, _gather(onehot_st, xcat)


def _bwd_kernel(has_w, window, si_ref, se_ref, av_ref, fi_ref, fe_ref,
                ids_ref, *rest):
    from jax.experimental import pallas as pl

    if has_w:
        w_ref, g_ref, *xwin_refs, dw_ref, p_ref = rest
    else:
        g_ref, p_ref = rest
        xwin_refs = ()

    s = pl.program_id(0)
    i = si_ref[s]

    @pl.when(fi_ref[s] == 1)
    def _init():
        p_ref[:] = jnp.zeros_like(p_ref)

    @pl.when(av_ref[s] == 1)
    def _acc():
        # without ``w`` the window one-hot carries the edge mask
        g_r, onehot_st, x_s = _bwd_gathers(
            window, i, ids_ref, g_ref, xwin_refs, masked=not has_w)
        m = g_r
        if has_w:
            dw = x_s * g_r
            # per-edge stream: overwrite on the edge block's first
            # accumulated visit (the memory is uninitialised — select,
            # never add), accumulate on a boundary block's later visits
            dw_ref[:] = jnp.where(fe_ref[s] == 1, dw, dw_ref[:] + dw)
            m = w_ref[:].astype(jnp.float32) * g_r
        # dx contributions of node block i's edges, still in WINDOW
        # coordinates (blocks i-hw..i+hw); overlap-added outside
        p_ref[:] += _scatter(onehot_st, m)                # [W*BN, F]


def _bwd_edge_block(f_pad, window):
    """Edge block of the backward pass: the ``w`` and ``dw`` blocks are
    ``[BE, F_pad]`` f32 each, double-buffered, beside ``[BE, F_pad]``
    temporaries and the ``[W * bn, F_pad]`` window blocks — at wide F a
    512-edge block overruns the default 16 MiB scoped VMEM (AOT-compiled
    for the v5e: 512 fits up to F_pad 1024 at window 3 and 512 at window 5,
    256 fits 1024 at window 5).  Halving divides ``_EDGE_BLOCK``, so the
    forward's padded operands serve unchanged."""
    return _EDGE_BLOCK if f_pad * window <= 2560 else _EDGE_BLOCK // 2


def _bwd_schedule(recv_f, n_blocks, bn, be, n_eblocks):
    """The dense schedule of the backward pass plus ``first_e`` (an edge
    block's first accumulated visit: per-edge output streams overwrite on
    it).  Every step that accumulates nothing (the forced step of an empty
    node block, the trailing clamped steps) HOLDS the last accumulated
    edge block: it fetches nothing and, above all, enters no per-edge
    output block — so such a block is entered exactly once, on consecutive
    steps, and initialised by its first accumulated visit."""
    step_i, step_eb, acc_valid, is_first, s_max = _dense_schedule(
        recv_f, n_blocks, bn, be, n_eblocks)
    held = jax.lax.cummax(jnp.where(acc_valid == 1, step_eb, -1))
    prev = jnp.concatenate([jnp.full(1, -1, jnp.int32), held[:-1]])
    first_e = ((acc_valid == 1) & (step_eb != prev)).astype(jnp.int32)
    step_eb = jnp.maximum(held, 0)
    return (step_i, step_eb, acc_valid, is_first, first_e), s_max


def _overlap_add(p, n_blocks, window, bn, f_pad):
    """``dx_p`` from the per-node-block window sums: slot k of node block
    i holds its edges' contributions to block i - hw + k; slots that fall
    off either end hold nothing (no sender is negative, parked edges are
    gated by g_r) and are dropped, not wrapped."""
    hw = window // 2
    p = p.reshape(n_blocks, window, bn, f_pad)
    dx_p = None
    for k in range(window):
        d = k - hw
        lo, hi = max(0, -d), n_blocks - max(0, d)
        if hi <= lo:
            continue
        part = jnp.pad(p[lo:hi, k],
                       ((lo + d, n_blocks - hi - d), (0, 0), (0, 0)))
        dx_p = part if dx_p is None else dx_p + part
    return dx_p.reshape(n_blocks * bn, f_pad)


def _bwd_call(has_w, window, x_p, w_p, ids_p, recv_f, g_p):
    """``(dx_p, dw_p)`` of the padded problem (``dw_p`` None without w,
    whose ``x_p`` and ``w_p`` are not read)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bn = _NODE_BLOCK
    n_pad, f_pad = g_p.shape
    e_pad = recv_f.shape[0]
    be = _bwd_edge_block(f_pad, window)
    n_blocks, n_eblocks = n_pad // bn, e_pad // be
    hw = window // 2
    tables, s_max = _bwd_schedule(recv_f, n_blocks, bn, be, n_eblocks)
    eix, xoff, _, outx = _window_maps(n_blocks)

    src = [w_p] if has_w else []
    in_specs = ([_ids_spec(be, eix)]
                + [pl.BlockSpec((be, f_pad), eix) for _ in src]
                + [pl.BlockSpec((bn, f_pad), outx)])
    operands = [ids_p, *src, g_p]
    out_specs = [pl.BlockSpec((window * bn, f_pad), outx)]
    out_shape = [jax.ShapeDtypeStruct(
        (n_blocks * window * bn, f_pad), jnp.float32)]
    if has_w:
        in_specs += [pl.BlockSpec((bn, f_pad), xoff(o))
                     for o in range(-hw, hw + 1)]
        operands += [x_p] * window
        out_specs.insert(0, pl.BlockSpec((be, f_pad), eix))
        out_shape.insert(0, jax.ShapeDtypeStruct(
            (e_pad, f_pad), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, has_w, window),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(s_max,),
            in_specs=in_specs,
            out_specs=out_specs,
        ),
        interpret=jax.default_backend() != "tpu",
        name="gather_mul_seg_bwd",
    )(*tables, *operands)

    dx_p = _overlap_add(outs[-1], n_blocks, window, bn, f_pad)
    if not has_w:
        return dx_p, None
    # edge blocks the schedule never accumulates (parked edges only) are
    # UNINITIALISED memory: select, never multiply (0 * NaN = NaN)
    dw_p = jnp.where(recv_f[:, None] < n_pad, outs[0], 0.0)
    return dx_p, dw_p


def _bwd_chain_kernel(chain, nw, window, want_dgeo, si_ref, se_ref, av_ref,
                      fi_ref, fe_ref, ids_ref, geo_ref, *rest):
    """:func:`_bwd_kernel` with the multiplier recomputed from its chain:
    ``dw = x_s * g_r`` stays in VMEM and is pulled back through the chain
    here (``jax.vjp`` on its body; weight VALUES upcast to f32 so their
    cotangents accumulate without per-step rounding) into constant-mapped
    f32 blocks zeroed at step 0 — and, only when the geometry is
    differentiated, into a per-edge ``dgeo`` stream with the first-visit
    select ``dw`` has in the array form."""
    from jax.experimental import pallas as pl

    w_refs = rest[:nw]
    g_ref = rest[nw]
    xwin_refs = rest[nw + 1:nw + 1 + window]
    outs = rest[nw + 1 + window:]
    dws_refs = outs[:nw]
    dgeo_ref = outs[nw] if want_dgeo else None
    p_ref = outs[-1]

    s = pl.program_id(0)
    i = si_ref[s]

    @pl.when(s == 0)
    def _init_w():
        for r in dws_refs:
            r[:] = jnp.zeros_like(r)

    @pl.when(fi_ref[s] == 1)
    def _init():
        p_ref[:] = jnp.zeros_like(p_ref)

    @pl.when(av_ref[s] == 1)
    def _acc():
        dt = xwin_refs[0].dtype
        # g_r's zero rows gate dw, hence the whole (linear) pullback:
        # every edge feeds the weight gradients exactly once
        g_r, onehot_st, x_s = _bwd_gathers(
            window, i, ids_ref, g_ref, xwin_refs)
        dw = x_s * g_r
        w_vals = tuple(r[:].astype(jnp.float32) for r in w_refs)
        geo = geo_ref[:]
        if want_dgeo:
            w, pull = jax.vjp(lambda wv, gv: chain(wv, gv, dt), w_vals, geo)
            dws, dgeo = pull(dw)
            dgeo_ref[:] = jnp.where(fe_ref[s] == 1, dgeo,
                                    dgeo_ref[:] + dgeo)
        else:
            w, pull = jax.vjp(lambda wv: chain(wv, geo, dt), w_vals)
            (dws,) = pull(dw)
        for r, d in zip(dws_refs, dws):
            r[:] += d
        p_ref[:] += _scatter(onehot_st, w * g_r)          # [W*BN, F]


def _bwd_chain_call(chain, window, x_p, geo_p, weights, ids_p, recv_f, g_p,
                    want_dgeo):
    """``(dx_p, dgeo_p, dweights)`` of the padded chain-form problem, one
    pass; ``dgeo_p`` is None (no E-sized output exists) unless asked."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bn = _NODE_BLOCK
    n_pad, f_pad = g_p.shape
    e_pad, gpw = geo_p.shape
    be = _chain_edge_block(f_pad, True)
    n_blocks, n_eblocks = n_pad // bn, e_pad // be
    hw = window // 2
    nw = len(weights)
    tables, s_max = _bwd_schedule(recv_f, n_blocks, bn, be, n_eblocks)
    eix, xoff, const, outx = _window_maps(n_blocks)

    in_specs = [
        _ids_spec(be, eix),
        pl.BlockSpec((be, gpw), eix),
    ] + [pl.BlockSpec(w.shape, const) for w in weights] \
      + [pl.BlockSpec((bn, f_pad), outx)] \
      + [pl.BlockSpec((bn, f_pad), xoff(o)) for o in range(-hw, hw + 1)]
    out_specs = [pl.BlockSpec(w.shape, const) for w in weights]
    out_shape = [jax.ShapeDtypeStruct(w.shape, jnp.float32)
                 for w in weights]
    if want_dgeo:
        out_specs.append(pl.BlockSpec((be, gpw), eix))
        out_shape.append(jax.ShapeDtypeStruct((e_pad, gpw), jnp.float32))
    out_specs.append(pl.BlockSpec((window * bn, f_pad), outx))
    out_shape.append(jax.ShapeDtypeStruct(
        (n_blocks * window * bn, f_pad), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_bwd_chain_kernel, chain, nw, window, want_dgeo),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(s_max,),
            in_specs=in_specs,
            out_specs=out_specs,
        ),
        interpret=jax.default_backend() != "tpu",
        name="gather_mul_seg_bwd",
        **_chain_vmem(f_pad),
    )(*tables, ids_p, geo_p, *weights, g_p, *([x_p] * window))

    dx_p = _overlap_add(outs[-1], n_blocks, window, bn, f_pad)
    # never-accumulated edge blocks are uninitialised memory: select
    dgeo_p = (jnp.where(recv_f[:, None] < n_pad, outs[nw], 0.0) if want_dgeo
              else None)
    return dx_p, dgeo_p, tuple(outs[:nw])


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _gms_padded(has_w, window, x_p, w_p, ids_p, recv_f):
    """The op on whole blocks: ``[N_pad, F_pad]`` f32 segment sums.  The
    public ops pad and slice round it in plain jnp, so AD zero-pads the
    cotangent and slices ``dx`` / ``dw`` by itself."""
    return _fwd_call(window, x_p, w_p, ids_p, recv_f)


def _gms_fwd(has_w, window, x_p, w_p, ids_p, recv_f):
    out = _fwd_call(window, x_p, w_p, ids_p, recv_f)
    # the w-less backward reads no x: hold its dtype, none of its rows
    return out, (x_p if has_w else x_p[:0], w_p, ids_p, recv_f)


def _gms_bwd(has_w, window, res, g_p):
    x_p, w_p, ids_p, recv_f = res
    dx_p, dw_p = _bwd_call(has_w, window, x_p, w_p, ids_p, recv_f, g_p)
    return (dx_p.astype(x_p.dtype),
            None if dw_p is None else dw_p.astype(w_p.dtype), None, None)


_gms_padded.defvjp(_gms_fwd, _gms_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _gcs_padded(chain, window, x_p, geo_p, weights, ids_p, recv_f):
    """:func:`_gms_padded` with the multiplier made in VMEM: ``geo_p``
    ``[E_pad, GPW]`` and the constant ``weights`` blocks take ``w_p``'s
    place, and ``chain(w_vals, geo, dt) -> [BE, F_pad]`` f32 (pure JAX,
    static) turns one block of them into one block of ``w``."""
    return _fwd_call(window, x_p, geo_p, ids_p, recv_f, chain,
                     tuple(weights))


def _gcs_fwd(chain, window, x_p, geo_p, weights, ids_p, recv_f):
    # symbolic_zeros: every argument arrives as (value, perturbed).  The
    # geometry's flag decides whether the backward writes a dgeo stream at
    # all; it reaches the backward rule as the residuals' STRUCTURE
    # (a pytree with no leaves), the one static channel the two rules share
    want_dgeo = () if geo_p.perturbed else None
    x_p, geo_p, ids_p, recv_f = (
        a.value for a in (x_p, geo_p, ids_p, recv_f))
    weights = tuple(w.value for w in weights)
    out = _fwd_call(window, x_p, geo_p, ids_p, recv_f, chain, weights)
    return out, (x_p, geo_p, weights, ids_p, recv_f, want_dgeo)


def _gcs_bwd(chain, window, res, g_p):
    x_p, geo_p, weights, ids_p, recv_f, want_dgeo = res
    dx_p, dgeo_p, dws = _bwd_chain_call(
        chain, window, x_p, geo_p, weights, ids_p, recv_f, g_p,
        want_dgeo is not None)
    return (dx_p.astype(x_p.dtype),
            None if dgeo_p is None else dgeo_p.astype(geo_p.dtype),
            tuple(d.astype(w.dtype) for d, w in zip(dws, weights)),
            None, None)


_gcs_padded.defvjp(_gcs_fwd, _gcs_bwd, symbolic_zeros=True)


def gather_mul_segment_sum(x, w, senders, receivers, window=3,
                           edge_valid=None):
    """``out[n, f] = sum_{e: recv[e]=n} x[send[e], f] * w[e, f]``.

    REQUIRES (collate invariants — see module docstring): nondecreasing
    ``receivers``; intra-graph edges, graphs contiguous, every graph within
    ``_NODE_BLOCK`` nodes; ``w`` pre-masked (zero rows on padding edges).
    No degree bound: the dense schedule processes every populated
    (node-block, edge-block) pair exactly once.  Exact (f32 accumulation,
    deterministic order); differentiable wrt x and w, both gradients from
    one pass over the edges in the order given (module docstring,
    "Backward").

    ``window`` (odd, static) widens the sender one-hot window: segment i
    gathers from blocks i-w//2..i+w//2 — 3 suffices for node-space message
    passing (graphs within one node block); DimeNet's triplet interaction
    runs in EDGE space where graphs span up to ~2 blocks and needs 5.

    ``edge_valid`` (optional int mask, 1 = real) lets the schedule SKIP
    masked-edge blocks outright (halves scheduled work at flagship
    padding ratios).  Contract: edge_valid == 0 edges carry zero ``w``
    rows and sort after all real edges (collate guarantees this).  A
    skipped edge contributes nothing forward, so its true gradient is
    zero, and its ``dw`` row is EXACTLY ZERO (the backward never visits
    its block and selects the unwritten memory away).  Without
    ``edge_valid`` a padding edge is an ordinary edge of the node it is
    parked on: its ``dw`` is that node's finite ``x * g``, which the
    caller's w-premask multiply kills.
    """
    n, f = x.shape
    out = _gms_padded(True, window,
                      *_pack(x, w, senders, receivers, None, edge_valid))
    return out[:n, :f].astype(x.dtype)


def gather_chain_segment_sum(x, geo_p, weights, chain, senders, receivers,
                             edge_valid=None, window=3):
    """:func:`gather_mul_segment_sum` whose multiplier is never an array:
    ``w[e] = chain(weights, geo_p[e])`` is evaluated per edge block in
    VMEM, forward and backward (module docstring, the third source).

    ``geo_p`` is the ``[E_pad, GPW]`` f32 geometry stream (rows padded to
    a whole number of ``_EDGE_BLOCK``s, lanes to whole 128-lane tiles),
    ``weights`` the tuple of packed constant blocks, ``chain`` a static
    pure-JAX ``(w_vals, geo, dt) -> [BE, F_pad]`` — callers pack with
    plain jnp ops (``lax.pad``, not scatters) so the raw operands'
    gradients fall out by AD (ops/scf_mp.py is the packing front for
    SchNet).  The chain must give masked (``edge_valid == 0``) edges a
    zero multiplier; like ``dw`` there, their ``dgeo`` rows are exactly
    zero.  Differentiable wrt ``x``, ``weights`` and ``geo_p``; the
    backward is ONE pass, and writes a ``dgeo`` stream only when
    ``geo_p`` is itself being differentiated."""
    n, f = x.shape
    n_pad = _round_up(n, _NODE_BLOCK)
    x_p = jnp.pad(x, ((0, n_pad - n), (0, _round_up(max(f, 1), 128) - f)))
    ids_p, recv_f = _pack_ids(senders, receivers, geo_p.shape[0], n_pad,
                              edge_valid)
    out = _gcs_padded(chain, window, x_p, geo_p, tuple(weights), ids_p,
                      recv_f)
    return out[:n, :f].astype(x.dtype)


def gather_segment_sum(x, senders, receivers, mask=None):
    """``out[n] = sum_{e: recv[e]=n} mask[e] * x[send[e]]`` — the w-less
    variant (GIN/MFC-style neighbor sum) with the same invariants as
    :func:`gather_mul_segment_sum`; ``mask`` is the [E] edge mask (padding
    edges contribute nothing — and their blocks are schedule-skipped, so
    mask == 0 edges must sort after all real edges, which collate
    guarantees).  Differentiable wrt ``x`` only; the backward is the same
    receiver-order pass without the ``dw`` stream."""
    n, f = x.shape
    out = _gms_padded(False, 3,
                      *_pack(x, None, senders, receivers, mask, mask))
    return out[:n, :f].astype(x.dtype)


# ---------------------------------------------------------------------------
# scatter-only variant: sorted segment sum on the dense schedule (no gather)
# — replaces XLA's sort-based scatter for already-edge-valued data (CGCNN's
# gated messages, PNA aggregates, masked pooling over node_gid)
# ---------------------------------------------------------------------------

def _scatter_kernel(si_ref, se_ref, av_ref, fi_ref, ids_ref, data_ref,
                    out_ref):
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    i = si_ref[s]

    @pl.when(fi_ref[s] == 1)
    def _init():
        out_ref[:] = jnp.zeros_like(out_ref)

    @pl.when(av_ref[s] == 1)
    def _acc():
        bn = out_ref.shape[0]
        be = ids_ref.shape[0]
        loc = ids_ref[:] - i * bn
        onehot = (loc == jax.lax.broadcasted_iota(
            jnp.int32, (be, bn), 1)).astype(jnp.float32)
        out_ref[:] += jax.lax.dot_general(
            onehot, data_ref[:].astype(jnp.float32),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _scatter_impl(data2d, sorted_ids, num_segments, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    e, f = data2d.shape
    bn, be = _NODE_BLOCK, _EDGE_BLOCK
    n_pad = _round_up(num_segments, bn)
    e_pad = _round_up(max(e, 1), be)
    f_pad = _round_up(max(f, 1), 128)
    n_blocks, n_eblocks = n_pad // bn, e_pad // be

    data_p = jnp.zeros((e_pad, f_pad), data2d.dtype).at[:e, :f].set(data2d)
    ids_p = jnp.full((e_pad, 1), n_pad, jnp.int32).at[:e, 0].set(
        sorted_ids.astype(jnp.int32))

    step_i, step_eb, acc_valid, is_first, s_max = _dense_schedule(
        ids_p[:, 0], n_blocks, bn, be, n_eblocks)

    def eix(s, si, se, av, fi):
        return (se[s], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s_max,),
        in_specs=[
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, f_pad), eix),
        ],
        out_specs=pl.BlockSpec(
            (bn, f_pad), lambda s, si, se, av, fi: (si[s], 0)),
    )
    out = pl.pallas_call(
        _scatter_kernel,
        out_shape=jax.ShapeDtypeStruct((n_pad, f_pad), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
        name="seg_sum_dense_fwd",
    )(step_i, step_eb, acc_valid, is_first, ids_p, data_p)
    return out[:num_segments, :f].astype(data2d.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def segment_sum_dense(data, sorted_ids, num_segments, valid=None):
    """Exact segment sum REQUIRING nondecreasing ``sorted_ids`` (collate's
    receivers / node_gid invariant) — one dense-schedule Pallas pass
    instead of XLA's sort-based scatter.  Any id distribution is processed
    exactly (no degree bound); out-of-range ids contribute nothing.
    ``valid`` (optional int mask, 1 = real) parks masked rows out of
    range so the schedule skips their blocks; masked rows must carry zero
    ``data`` and sort last (collate guarantees both for padding edges).
    Differentiable wrt ``data``."""
    shape = data.shape
    interpret = jax.default_backend() != "tpu"
    if valid is not None:
        sorted_ids = jnp.where(valid != 0, sorted_ids, num_segments)
    out = _scatter_impl(
        data.reshape(shape[0], -1), sorted_ids, num_segments, interpret)
    return out.reshape((num_segments,) + shape[1:])


def _ssd_fwd(data, sorted_ids, num_segments, valid=None):
    if valid is not None:
        sorted_ids = jnp.where(valid != 0, sorted_ids, num_segments)
    return segment_sum_dense(data, sorted_ids, num_segments), (
        sorted_ids, data.shape)


def _ssd_bwd(num_segments, res, g):
    sorted_ids, shape = res
    g2 = g.reshape(num_segments, -1)
    ok = (sorted_ids >= 0) & (sorted_ids < num_segments)
    safe = jnp.clip(sorted_ids, 0, num_segments - 1)
    d = jnp.where(ok[:, None], g2[safe], 0.0)
    return d.reshape(shape), None, None


segment_sum_dense.defvjp(_ssd_fwd, _ssd_bwd)
