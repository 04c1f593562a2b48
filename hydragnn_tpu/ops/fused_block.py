"""Universal fused message-passing builder: ONE dense-schedule Pallas
engine for every gather -> edge-chain -> gate -> segment-reduce block.

PR 2 (poly_mp), the SchNet cfconv pipeline (scf_mp), the fused EGCL block
(egcl_mp) and the DimeNet row-MLP tail were four hand-written instances of
the same shape, each reimplementing the sorted one-hot placement, the
3-block gather window, the masked-edge schedule skip and the two-pass
no-[E,H]-in-HBM VJP.  This module owns that machinery once and emits both
the forward kernel and the custom VJP from a declarative
:class:`EdgeBlockSpec`:

  * ``chain(w_vals, geo, xp, xo, dt) -> tuple of [BE, Wk]`` — the per-edge
    math, written once as plain JAX.  The backward is derived with
    ``jax.vjp`` INSIDE the kernel (ref reads are tracers, so the pullback
    traces into the same Pallas body — flash-attention-style recompute
    with no [E, H] HBM stream, and no hand-derived transposes to keep in
    sync with the forward).
  * ``primary`` names the scatter side ("sender" or "receiver"); the edge
    stream is processed sorted by it, making both scatters block-local
    one-hot matmuls, while the other side rides a ±hw-block window
    (collate invariant: graphs never straddle a node block; DimeNet's
    edge-space triplets span up to 2, hence ``window``).

Backward splits into the two passes every retired kernel used:

  pass P (primary-sorted): recompute the chain, gather the cotangent
    through the primary one-hot (zero rows gate the whole pullback — an
    out-of-block edge contributes nothing this visit), then
    ``jax.vjp`` wrt (weights, geo, x_primary): weight grads accumulate
    in-kernel into constant-mapped f32 blocks, dgeo streams per edge
    (first-visit init, forced-empty-block re-init), dx_primary scatters
    through the same one-hot.  Weight values are upcast to f32 BEFORE the
    vjp so their cotangents accumulate without per-step rounding, while
    the refs stay bf16 under a bf16 policy (``_dot`` recasts operands to
    the compute dtype for the MXU).
  pass S (other-sorted): recompute, cotangent gathered through the
    window, ``jax.vjp`` wrt x_other ONLY — the pullback jaxpr contains no
    wasted weight/geo transposes.

Masked edges (em == 0) are parked on the out-of-range sentinel node in
BOTH id columns, so the dense schedule never visits their blocks: outputs
and every grad are exactly zero by construction (uninitialized per-edge
stream rows are ``where``-selected to zero — never multiplied, since
0 * NaN = NaN).  Contract: masked edges tail-sort in both edge orderings
(collate parks them on node N-1, the maximum id).

Geometry lanes: the builder pads ``geo`` to a whole number of 128-lane
tiles with a constant-1.0 bias lane LAST — specs fold biases onto the
matching weight row, and bias grads fall out of the weight-block
cotangent for free.

The per-moment aggregation kernels (poly_mp) and the trivial-chain
gather/scatter ops (fused_mp) keep their specialized bodies — their
chains are identity/multiply and already share this module's schedule.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional, Tuple, Union

import jax
import jax.numpy as jnp

from hydragnn_tpu.ops.aggregate import _round_up, block_ranges

_NODE_BLOCK = 128   # rows of out per grid step (gather window = W x this)
_GP = 128           # one geometry lane tile; widths are multiples of this


# ---------------------------------------------------------------------------
# dense schedule (canonical home; fused_mp/poly_mp/gat_mp import from here)
# ---------------------------------------------------------------------------


def _dense_schedule(sorted_ids, n_blocks, bn, be, n_eblocks):
    """DENSE grid schedule: one step per (node-block, populated edge-block)
    pair, flattened CSR-style into scalar-prefetched step tables — instead
    of a rectangular (n_blocks, k_max) grid whose bound-degree worst case
    makes most steps no-op DMAs.  Empty blocks get exactly one step (their
    out must still be zeroed).  Total steps are UNCONDITIONALLY bounded:
    ranges tile the edge blocks with at most one shared boundary block per
    adjacent pair, so sum(max(range_i, 1)) <= n_eblocks + 2*n_blocks
    regardless of degree distribution — no degree contract, no dropped
    edges, no overflow case at all.

    Returns (step_i, step_eb, acc_valid, is_first, s_max)."""
    start, end = block_ranges(sorted_ids, n_blocks, bn, be, n_eblocks)
    counts = end - start
    steps = jnp.maximum(counts, 1)
    offsets = jnp.cumsum(steps)
    total = offsets[-1]
    s_max = n_eblocks + 2 * n_blocks
    s_idx = jnp.arange(s_max, dtype=jnp.int32)
    step_i = jnp.minimum(
        jnp.searchsorted(offsets, s_idx, side="right"),
        n_blocks - 1).astype(jnp.int32)
    block_off = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), offsets[:-1].astype(jnp.int32)])
    k = s_idx - block_off[step_i]
    step_eb = jnp.clip(start[step_i] + k, 0, n_eblocks - 1).astype(jnp.int32)
    # accumulate only on real (block, edge-block) pairs; the forced step of
    # an empty block and the trailing padding steps (which clamp onto the
    # last block and re-read its final edge block — a cached DMA) are no-ops
    acc_valid = ((k < counts[step_i]) & (s_idx < total)).astype(jnp.int32)
    prev_i = jnp.concatenate([jnp.full(1, -1, jnp.int32), step_i[:-1]])
    is_first = (step_i != prev_i).astype(jnp.int32)
    return step_i, step_eb, acc_valid, is_first, s_max


def _first_eb(step_eb):
    """First visit of each edge block (per-edge output streams re-init on
    it; a boundary block's second visit accumulates)."""
    prev_eb = jnp.concatenate([jnp.full(1, -1, jnp.int32), step_eb[:-1]])
    return (step_eb != prev_eb).astype(jnp.int32)


def _window_maps(n_blocks):
    # variadic: pass P prefetches five scalar tables, fwd/pass S four
    def eix(s, si, se, *rest):
        return (se[s], 0)

    def xoff(off):
        def f(s, si, se, *rest):
            return (jnp.clip(si[s] + off, 0, n_blocks - 1), 0)
        return f

    def const(s, *rest):
        return (0, 0)

    def outx(s, si, se, *rest):
        return (si[s], 0)

    return eix, xoff, const, outx


# ---------------------------------------------------------------------------
# shared in-kernel primitives
# ---------------------------------------------------------------------------


def _ssp(x):
    """shifted softplus, f32, matching models/layers.shifted_softplus."""
    return jax.nn.softplus(x) - 0.6931471805599453


def _dot(a, b, dims, dt):
    """MXU dot with operands in the compute dtype and f32 accumulation.

    Measured NEUTRAL on the v5e (173.9 -> 173.2 ms at dense h1024):
    JAX's default matmul precision already runs f32 dots through the MXU
    as bf16 passes, so explicit bf16 operands buy no rate — kept because
    it makes the operand dtype explicit and lets the constant weight
    blocks and one-hots live in bf16 VMEM (per-step-produced f32
    operands still pay one downcast; accumulation and every elementwise
    stays f32).

    bf16 operands pin the contraction to DEFAULT precision: a bf16
    product is exact in the f32 accumulator, so a higher contract
    precision has nothing to add — and Mosaic (libtpu 0.0.34) REJECTS
    bf16 operands under an fp32 contract precision ("Bad lhs type"),
    which an ambient ``jax.default_matmul_precision("highest")`` would
    otherwise request for every dot traced into the kernel.  f32
    operands keep following the ambient precision."""
    return jax.lax.dot_general(
        a.astype(dt), b.astype(dt), (dims, ((), ())),
        precision=(jax.lax.Precision.DEFAULT if dt == jnp.bfloat16
                   else None),
        preferred_element_type=jnp.float32)


def _onehot_local(idx_ref, i, bn, dt):
    """Block-local one-hot [BE, BN] of global ids against node block ``i``.
    Out-of-block ids produce an all-zero row — such edges contribute
    nothing this visit (they are in-block for exactly one visiting node
    block)."""
    be = idx_ref.shape[0]
    loc = idx_ref[:] - i * bn
    return (loc == jax.lax.broadcasted_iota(
        jnp.int32, (be, bn), 1)).astype(dt)


def _gather_local(idx_ref, blk_ref, i, bn, dt):
    """Block-local one-hot gather: rows of ``blk_ref`` (node block ``i``)
    at global ids ``idx``; returns ([BE, F] f32 gathered, [BE, BN]
    one-hot — the transposed one-hot gates the matching scatter)."""
    onehot = _onehot_local(idx_ref, i, bn, dt)
    return _dot(onehot, blk_ref[:], ((1,), (0,)), dt), onehot


def _gather_window(idx_ref, win_refs, base_block, bn):
    """One-hot window gather: rows of concat(win_refs) at idx (global node
    ids), returning ([BE, F] gathered, [BE, W*BN] onehot)."""
    be = idx_ref.shape[0]
    w = len(win_refs)
    base = base_block * bn
    loc = idx_ref[:] - base
    dt = win_refs[0].dtype  # 0/1 one-hot is exact in any dtype
    onehot = (loc == jax.lax.broadcasted_iota(
        jnp.int32, (be, w * bn), 1)).astype(dt)
    cat = jnp.concatenate([r[:] for r in win_refs], axis=0)
    out = _dot(onehot, cat, ((1,), (0,)), dt)
    return out, onehot


def _pack_geo(geo, em, p_ids, o_ids, e_pad, n_pad, gpw):
    """Pad the geometry stream to ``gpw`` lanes with the constant-1.0 bias
    lane LAST, and park masked edges (em == 0) on the out-of-range
    sentinel node ``n_pad`` in both id columns so the dense schedule
    assigns their blocks to NO node block and never visits them — at
    flagship collate shapes HALF the edge slots are batch padding, so the
    skip halves the scheduled MXU work.  Their outputs and grads are
    exactly zero by construction."""
    e, gd = geo.shape
    geo_p = jnp.zeros((e_pad, gpw), jnp.float32)
    if gd:
        geo_p = geo_p.at[:e, :gd].set(geo.astype(jnp.float32))
    geo_p = geo_p.at[:, gpw - 1].set(1.0)
    valid = em != 0
    p_p = jnp.full((e_pad, 1), n_pad, jnp.int32).at[:e, 0].set(
        jnp.where(valid, p_ids, n_pad).astype(jnp.int32))
    o_p = jnp.full((e_pad, 1), n_pad, jnp.int32).at[:e, 0].set(
        jnp.where(valid, o_ids, n_pad).astype(jnp.int32))
    return geo_p, p_p, o_p


# ---------------------------------------------------------------------------
# the declarative spec
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class EdgeBlockSpec:
    """Declarative fused edge block.

    ``chain(w_vals, geo, xp, xo, dt) -> tuple of [BE, Wk] f32`` is the
    per-edge math: ``w_vals`` the packed weight-block VALUES (biases
    folded onto the geometry bias lane's weight row or carried as [8, H]
    row-broadcast blocks used via ``b[0:1, :]``), ``geo`` the padded
    [BE, GPW] f32 geometry tile(s) (bias lane ``GPW - 1`` constant 1.0),
    ``xp``/``xo`` the gathered primary/other node features ([BE, F] f32,
    or None when the matching gather flag is off), ``dt`` the compute
    dtype for ``_dot``.  Every output is scattered (segment-summed) onto
    the PRIMARY node side.  The chain must be pure JAX — the builder
    derives the whole backward from it with ``jax.vjp``.

    ``edge_block`` / ``edge_block_p`` (pass P may need a smaller block:
    its weight-grad accumulators double the resident VMEM) are ints or
    ``f(f_pad, bf16) -> int`` callables."""
    name: str
    primary: str                      # "sender" | "receiver"
    gather_primary: bool
    gather_other: bool
    num_outputs: int
    chain: Callable[..., Tuple[Any, ...]]
    window: int = 3
    edge_block: Union[int, Callable[[int, bool], int]] = 256
    edge_block_p: Optional[Union[int, Callable[[int, bool], int]]] = None

    def __post_init__(self):
        assert self.primary in ("sender", "receiver"), self.primary
        assert self.window % 2 == 1, "window must be odd"
        assert self.gather_primary or self.gather_other, self.name


def _resolve_be(eb, f_pad, bf16):
    return eb(f_pad, bf16) if callable(eb) else eb


def _primary_order(spec, geo, em, senders, receivers, sender_perm):
    """(geo, em, p_ids, o_ids) in the primary-sorted edge ordering."""
    if spec.primary == "sender":
        if sender_perm is None:
            sender_perm = jnp.argsort(senders, stable=True)
        return (geo[sender_perm], em[sender_perm], senders[sender_perm],
                receivers[sender_perm], sender_perm)
    return geo, em, receivers, senders, sender_perm


def _other_order(spec, geo, em, senders, receivers, sender_perm):
    """(geo, em, sorted_ids, window_ids) in the OTHER-side ordering for
    pass S: the sorted side is the other/gathered side, the primary side
    (where cotangents live) rides the window."""
    if spec.primary == "sender":
        return geo, em, receivers, senders     # natural receiver order
    if sender_perm is None:
        sender_perm = jnp.argsort(senders, stable=True)
    return (geo[sender_perm], em[sender_perm], senders[sender_perm],
            receivers[sender_perm])


# ---------------------------------------------------------------------------
# generic kernels
# ---------------------------------------------------------------------------


def _fwd_kernel(spec, nw, si_ref, se_ref, av_ref, fi_ref,
                p_ref, o_ref, geo_ref, *rest):
    from jax.experimental import pallas as pl

    w_refs = rest[:nw]
    win_refs = rest[nw:nw + spec.window]
    out_refs = rest[nw + spec.window:]

    s = pl.program_id(0)
    i = si_ref[s]

    @pl.when(fi_ref[s] == 1)
    def _init():
        for r in out_refs:
            r[:] = jnp.zeros_like(r)

    @pl.when(av_ref[s] == 1)
    def _acc():
        bn = out_refs[0].shape[0]
        dt = win_refs[0].dtype
        hw = spec.window // 2
        if spec.gather_primary:
            xp, onehot_p = _gather_local(p_ref, win_refs[hw], i, bn, dt)
        else:
            xp, onehot_p = None, _onehot_local(p_ref, i, bn, dt)
        xo = (_gather_window(o_ref, win_refs, i - hw, bn)[0]
              if spec.gather_other else None)
        w_vals = tuple(r[:] for r in w_refs)
        outs = spec.chain(w_vals, geo_ref[:], xp, xo, dt)
        for r, o in zip(out_refs, outs):
            r[:] += _dot(onehot_p, o, ((0,), (0,)), dt)


def _bwd_p_kernel(spec, nw, si_ref, se_ref, av_ref, fi_ref, feb_ref,
                  p_ref, o_ref, geo_ref, *rest):
    from jax.experimental import pallas as pl

    k = spec.num_outputs
    w_refs = rest[:nw]
    win_refs = rest[nw:nw + spec.window]
    ct_refs = rest[nw + spec.window:nw + spec.window + k]
    outs = rest[nw + spec.window + k:]
    dw_refs = outs[:nw]
    dgeo_ref = outs[nw]
    dx_ref = outs[nw + 1] if spec.gather_primary else None

    s = pl.program_id(0)
    i = si_ref[s]

    @pl.when(s == 0)
    def _init_w():
        for r in dw_refs:
            r[:] = jnp.zeros_like(r)

    if spec.gather_primary:
        @pl.when(fi_ref[s] == 1)
        def _init_x():
            dx_ref[:] = jnp.zeros_like(dx_ref)

    @pl.when(av_ref[s] == 1)
    def _acc():
        bn = win_refs[0].shape[0]
        dt = win_refs[0].dtype
        hw = spec.window // 2
        if spec.gather_primary:
            xp, onehot_p = _gather_local(p_ref, win_refs[hw], i, bn, dt)
        else:
            xp, onehot_p = None, _onehot_local(p_ref, i, bn, dt)
        xo = (_gather_window(o_ref, win_refs, i - hw, bn)[0]
              if spec.gather_other else None)
        # weight VALUES upcast to f32 so their cotangents come back f32
        # (accumulate without per-step rounding); the chain's _dot recasts
        # operands to the compute dtype for the MXU
        w_vals = tuple(r[:].astype(jnp.float32) for r in w_refs)
        geo_val = geo_ref[:]
        # cotangents gathered at the SORTED side gate everything: an edge
        # whose primary node is out of this block gets an all-zero ct row,
        # and the pullback is linear in it — zero grads this visit (its
        # in-block visit supplies them)
        cts = tuple(_dot(onehot_p, c[:], ((1,), (0,)), dt)
                    for c in ct_refs)
        if spec.gather_primary:
            def fn(wv, g, xpv):
                return spec.chain(wv, g, xpv, xo, dt)
            _, pull = jax.vjp(fn, w_vals, geo_val, xp)
            dws, dgeo_v, dxp = pull(cts)
        else:
            def fn(wv, g):
                return spec.chain(wv, g, None, xo, dt)
            _, pull = jax.vjp(fn, w_vals, geo_val)
            dws, dgeo_v = pull(cts)
        for r, d in zip(dw_refs, dws):
            r[:] += d
        dgeo_ref[:] = jnp.where(feb_ref[s] == 1, dgeo_v,
                                dgeo_ref[:] + dgeo_v)
        if spec.gather_primary:
            dx_ref[:] += _dot(onehot_p, dxp, ((0,), (0,)), dt)

    # a freshly-entered edge block that is NOT accumulated this step (the
    # forced step of an empty node block) must still be initialized, or a
    # boundary block's second visit would accumulate onto garbage
    @pl.when((av_ref[s] == 0) & (feb_ref[s] == 1))
    def _init_e():
        dgeo_ref[:] = jnp.zeros_like(dgeo_ref)


def _bwd_s_kernel(spec, nw, si_ref, se_ref, av_ref, fi_ref,
                  sord_ref, wside_ref, geo_ref, *rest):
    from jax.experimental import pallas as pl

    k = spec.num_outputs
    w = spec.window
    w_refs = rest[:nw]
    win_refs = rest[nw:nw + w]
    ct_wins = [rest[nw + w + j * w:nw + w + (j + 1) * w] for j in range(k)]
    dx_ref = rest[nw + w + k * w]

    s = pl.program_id(0)
    i = si_ref[s]

    @pl.when(fi_ref[s] == 1)
    def _init():
        dx_ref[:] = jnp.zeros_like(dx_ref)

    @pl.when(av_ref[s] == 1)
    def _acc():
        bn = dx_ref.shape[0]
        dt = win_refs[0].dtype
        hw = w // 2
        # roles swapped: the other/gathered side is sorted (output rows),
        # the primary side — cotangents included — rides the window
        xo, onehot_o = _gather_local(sord_ref, win_refs[hw], i, bn, dt)
        xp = (_gather_window(wside_ref, win_refs, i - hw, bn)[0]
              if spec.gather_primary else None)
        w_vals = tuple(r[:] for r in w_refs)
        geo_val = geo_ref[:]
        cts = tuple(_gather_window(wside_ref, cw, i - hw, bn)[0]
                    for cw in ct_wins)

        def fn(xov):
            return spec.chain(w_vals, geo_val, xp, xov, dt)

        _, pull = jax.vjp(fn, xo)
        (dxo,) = pull(cts)
        dx_ref[:] += _dot(onehot_o, dxo, ((0,), (0,)), dt)


# ---------------------------------------------------------------------------
# builder
# ---------------------------------------------------------------------------


def _out_widths(spec, weights, gpw, f_pad, be, dt):
    """Static chain output widths via abstract evaluation — specs never
    declare shapes the chain already implies."""
    w_avals = tuple(jax.ShapeDtypeStruct(w.shape, jnp.float32)
                    for w in weights)
    geo_aval = jax.ShapeDtypeStruct((be, gpw), jnp.float32)
    x_aval = jax.ShapeDtypeStruct((be, f_pad), jnp.float32)
    outs = jax.eval_shape(
        lambda wv, g, xp, xo: spec.chain(wv, g, xp, xo, dt),
        w_avals, geo_aval,
        x_aval if spec.gather_primary else None,
        x_aval if spec.gather_other else None)
    assert len(outs) == spec.num_outputs, (spec.name, len(outs))
    return tuple(o.shape[1] for o in outs)


def _fused_fwd(spec, x, geo, em, weights, senders, receivers, sender_perm,
               interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, f = x.shape
    e, gd = geo.shape
    bf16 = x.dtype == jnp.bfloat16
    f_pad = _round_up(max(f, 1), 128)
    gpw = _round_up(gd + 1, _GP)
    bn = _NODE_BLOCK
    be = _resolve_be(spec.edge_block, f_pad, bf16)
    n_pad = _round_up(n, bn)
    e_pad = _round_up(max(e, 1), be)
    n_blocks, n_eblocks = n_pad // bn, e_pad // be

    x_p = jnp.zeros((n_pad, f_pad), x.dtype).at[:n, :f].set(x)
    geo_o, em_o, p_ids, o_ids, _ = _primary_order(
        spec, geo, em, senders, receivers, sender_perm)
    geo_p, p_p, o_p = _pack_geo(geo_o, em_o, p_ids, o_ids, e_pad, n_pad, gpw)

    step_i, step_eb, acc_valid, is_first, s_max = _dense_schedule(
        p_p[:, 0], n_blocks, bn, be, n_eblocks)
    eix, xoff, const, outx = _window_maps(n_blocks)
    hw = spec.window // 2

    dt = jnp.bfloat16 if bf16 else jnp.float32
    widths = _out_widths(spec, weights, gpw, f_pad, be, dt)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s_max,),
        in_specs=[
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, gpw), eix),
        ] + [pl.BlockSpec(w.shape, const) for w in weights]
        + [pl.BlockSpec((bn, f_pad), xoff(o)) for o in range(-hw, hw + 1)],
        out_specs=[pl.BlockSpec((bn, wk), outx) for wk in widths],
    )
    outs = pl.pallas_call(
        functools.partial(_fwd_kernel, spec, len(weights)),
        out_shape=[jax.ShapeDtypeStruct((n_pad, wk), jnp.float32)
                   for wk in widths],
        grid_spec=grid_spec,
        interpret=interpret,
        name=f"{spec.name}_fwd",
    )(step_i, step_eb, acc_valid, is_first, p_p, o_p, geo_p,
      *weights, *([x_p] * spec.window))
    return tuple(outs)


def _fused_bwd(spec, res, cts):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    x, geo, em, weights, senders, receivers, sender_perm = res
    interpret = jax.default_backend() != "tpu"
    n, f = x.shape
    e, gd = geo.shape
    bf16 = x.dtype == jnp.bfloat16
    f_pad = _round_up(max(f, 1), 128)
    gpw = _round_up(gd + 1, _GP)
    bn = _NODE_BLOCK
    be_p = _resolve_be(spec.edge_block_p or spec.edge_block, f_pad, bf16)
    be_s = _resolve_be(spec.edge_block, f_pad, bf16)
    n_pad = _round_up(n, bn)
    hw = spec.window // 2
    k = spec.num_outputs
    nw = len(weights)

    x_p = jnp.zeros((n_pad, f_pad), x.dtype).at[:n, :f].set(x)
    # cotangents ride HBM<->VMEM in the compute dtype like the windows
    ct_ps = tuple(c.astype(x.dtype) for c in cts)
    eix, xoff, const, outx = _window_maps(n_pad // bn)

    # ---- pass P: primary-sorted — weight grads, dgeo, primary-side dx ----
    e_pad = _round_up(max(e, 1), be_p)
    n_blocks, n_eblocks = n_pad // bn, e_pad // be_p
    geo_o, em_o, p_ids, o_ids, perm = _primary_order(
        spec, geo, em, senders, receivers, sender_perm)
    geo_p, p_p, o_p = _pack_geo(geo_o, em_o, p_ids, o_ids, e_pad, n_pad, gpw)
    step_i, step_eb, acc_valid, is_first, s_max = _dense_schedule(
        p_p[:, 0], n_blocks, bn, be_p, n_eblocks)
    feb = _first_eb(step_eb)

    in_specs_p = [
        pl.BlockSpec((be_p, 1), eix),
        pl.BlockSpec((be_p, 1), eix),
        pl.BlockSpec((be_p, gpw), eix),
    ] + [pl.BlockSpec(w.shape, const) for w in weights] \
      + [pl.BlockSpec((bn, f_pad), xoff(o)) for o in range(-hw, hw + 1)] \
      + [pl.BlockSpec((bn, c.shape[1]), xoff(0)) for c in ct_ps]
    out_specs_p = [pl.BlockSpec(w.shape, const) for w in weights] \
        + [pl.BlockSpec((be_p, gpw), eix)]
    out_shape_p = [jax.ShapeDtypeStruct(w.shape, jnp.float32)
                   for w in weights] \
        + [jax.ShapeDtypeStruct((e_pad, gpw), jnp.float32)]
    if spec.gather_primary:
        out_specs_p.append(pl.BlockSpec((bn, f_pad), outx))
        out_shape_p.append(jax.ShapeDtypeStruct((n_pad, f_pad), jnp.float32))
    grid_p = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(s_max,),
        in_specs=in_specs_p,
        out_specs=out_specs_p,
    )
    outs_p = pl.pallas_call(
        functools.partial(_bwd_p_kernel, spec, nw),
        out_shape=out_shape_p,
        grid_spec=grid_p,
        interpret=interpret,
        name=f"{spec.name}_bwd_p",
    )(step_i, step_eb, acc_valid, is_first, feb,
      p_p, o_p, geo_p, *weights, *([x_p] * spec.window), *ct_ps)
    dws_p = outs_p[:nw]
    dgeo_p = outs_p[nw]
    dxp_p = outs_p[nw + 1] if spec.gather_primary else None

    # ---- pass S: other-sorted — other-side dx ----
    dxo_p = None
    if spec.gather_other:
        e_pad_s = _round_up(max(e, 1), be_s)
        n_eblocks_s = e_pad_s // be_s
        geo_s, em_s, sord, wside = _other_order(
            spec, geo, em, senders, receivers, sender_perm)
        geo_sp, sord_p, wside_p = _pack_geo(
            geo_s, em_s, sord, wside, e_pad_s, n_pad, gpw)
        step_i2, step_eb2, acc_valid2, is_first2, s_max2 = _dense_schedule(
            sord_p[:, 0], n_blocks, bn, be_s, n_eblocks_s)
        in_specs_s = [
            pl.BlockSpec((be_s, 1), eix),
            pl.BlockSpec((be_s, 1), eix),
            pl.BlockSpec((be_s, gpw), eix),
        ] + [pl.BlockSpec(w.shape, const) for w in weights] \
          + [pl.BlockSpec((bn, f_pad), xoff(o))
             for o in range(-hw, hw + 1)] \
          + [pl.BlockSpec((bn, c.shape[1]), xoff(o))
             for c in ct_ps for o in range(-hw, hw + 1)]
        grid_s = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(s_max2,),
            in_specs=in_specs_s,
            out_specs=pl.BlockSpec((bn, f_pad), outx),
        )
        ct_wins = [c for c in ct_ps for _ in range(spec.window)]
        dxo_p = pl.pallas_call(
            functools.partial(_bwd_s_kernel, spec, nw),
            out_shape=jax.ShapeDtypeStruct((n_pad, f_pad), jnp.float32),
            grid_spec=grid_s,
            interpret=interpret,
            name=f"{spec.name}_bwd_s",
        )(step_i2, step_eb2, acc_valid2, is_first2,
          sord_p, wside_p, geo_sp, *weights, *([x_p] * spec.window),
          *ct_wins)

    if dxp_p is not None and dxo_p is not None:
        dx = (dxp_p[:n, :f] + dxo_p[:n, :f]).astype(x.dtype)
    else:
        dx = (dxp_p if dxp_p is not None else dxo_p)[:n, :f].astype(x.dtype)

    # pass P ran in primary order: un-permute the per-edge stream if the
    # primary side was the sorted-sender one, then `where`-select masked
    # rows to zero — their blocks are never visited so the memory is
    # uninitialized (a multiply would propagate NaN bits)
    if spec.primary == "sender":
        dgeo_nat = jnp.zeros((e, gpw), jnp.float32).at[perm].set(dgeo_p[:e])
    else:
        dgeo_nat = dgeo_p[:e]
    valid = (em != 0)[:, None]
    dgeo = jnp.where(valid, dgeo_nat[:, :gd], 0.0).astype(geo.dtype)
    dweights = tuple(d.astype(w.dtype) for d, w in zip(dws_p, weights))
    return dx, dgeo, None, dweights, None, None, None


def build_fused_edge_op(spec: EdgeBlockSpec):
    """Emit the fused op for ``spec``: forward Pallas pass + two-pass
    custom VJP.

    ``op(x, geo, em, weights, senders, receivers, sender_perm)`` returns
    a tuple of [N_pad, Wk] f32 segment sums on the primary side (callers
    slice ``[:n, :w]`` and cast — the slice's AD zero-pads cotangents).
    ``weights`` is the tuple of PACKED weight blocks (callers pack with
    plain jnp ops so raw-parameter grads fall out of the padded-block
    cotangent by AD).  Differentiable wrt x, geo and weights.

    Requires the collate invariants (nondecreasing receivers, intra-graph
    edges, graphs within one node block — ``spec.window`` blocks for
    edge-space specs — and the host-precomputed stable sender argsort);
    ``em`` is the int edge-validity mask: em == 0 edges are
    schedule-skipped entirely and get EXACTLY ZERO for every output and
    grad."""

    @jax.custom_vjp
    def op(x, geo, em, weights, senders, receivers, sender_perm):
        interpret = jax.default_backend() != "tpu"
        return _fused_fwd(spec, x, geo, em, tuple(weights), senders,
                          receivers, sender_perm, interpret)

    def fwd(x, geo, em, weights, senders, receivers, sender_perm):
        out = op(x, geo, em, weights, senders, receivers, sender_perm)
        return out, (x, geo, em, tuple(weights), senders, receivers,
                     sender_perm)

    def bwd(res, cts):
        return _fused_bwd(spec, res, cts)

    op.defvjp(fwd, bwd)
    op.spec = spec
    return op


# ---------------------------------------------------------------------------
# unified dispatch-layer fallback telemetry
# ---------------------------------------------------------------------------


def note_fallback(arch: str, reason: str, **fields) -> None:
    """Record a one-shot fused-path fallback for the unified
    ``fused_fallback`` health event ({arch, reason} + spec fields) —
    every arch's dispatch gate funnels through here instead of minting
    per-arch kinds (``egcl_fallback`` is kept as an alias for one
    release; the trainer emits both)."""
    from hydragnn_tpu.telemetry import pipeline

    pipeline.record_fallback("fused", arch=arch, reason=reason, **fields)
