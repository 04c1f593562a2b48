"""Fused GATv2 edge attention: gather -> logits -> online softmax ->
weighted aggregation in ONE Pallas pass (round-4 VERDICT item 3).

The composed implementation (models/gat.py round 3) spends ~10.7 ms/layer
fwd+bwd at the v5e sweep shapes across five separate segment ops (two
logits gathers, segment max, denominator scatter, weighted aggregation),
each materializing [E, H*F] or [E, H] intermediates in HBM.  This kernel
computes the whole edge-side attention in one dense-schedule pass over the
receiver-sorted edge blocks (the same CSR-style scalar-prefetched schedule
as ops/fused_mp.py), flash-attention style:

  for each node block i (rows of out), iterating its edge blocks:
      xs = one-hot window gather of xl at senders     (3-block locality)
      xt = one-hot gather of xr at receivers          (block-local)
      e  = leaky_relu(xs + xt) @ att_mat              [BE, H]   (MXU)
      online-rescale (m, d, acc) with p = exp(e - m); the numerator uses
      the caller's dropout bits
  returns acc[n] = sum_e p_e b_e xl[src_e],  m[n] = max_e e_e,
          d[n]   = sum_e p_e          (softmax-then-dropout convention:
                                       the denominator ignores dropout)

The SELF-LOOP term and the final normalization are merged OUTSIDE in plain
jnp (models/gat.py): softmax shift-invariance makes ``stop_gradient(m)``
exact there, so the merge is ordinary autodiff'd elementwise code.

Backward (custom VJP, no [E, H*F] HBM intermediates): with m frozen,
  dL/de_k      = p_k (b_k <ga[r], xl[s]>_h + gd[r, h])
  dxl[s]      += p_k b_k ga[r] + dz_k        (pass S, sender-sorted)
  dxr[r]      += dz_k                        (pass R, receiver-sorted)
  datt_mat    += z^T de                      (pass R, accumulated)
  dz_k         = (de_k @ att_mat^T) * leaky_relu'(xs + xt)
Both passes recompute z/e/p from the saved inputs (flash-attention's
recompute-over-store trade), so only [N, .] arrays ever hit HBM.

Invariants REQUIRED (same as fused_mp): receivers nondecreasing; graphs
contiguous and within one node block, so a triple-block window covers
every edge's other endpoint; ``sender_perm`` = stable argsort of senders
(collate's ``edge_perm_sender``).  Reference: GATStack.py:87-113 + PyG
GATv2Conv.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from hydragnn_tpu.ops.aggregate import _round_up
from hydragnn_tpu.ops.fused_block import (
    _NODE_BLOCK, _dense_schedule)
from hydragnn_tpu.ops.fused_block import _window_maps as _shared_window_maps

_EDGE_BLOCK = 512

# Widest flat head-feature width (h*f) ONE fused kernel call compiles for:
# the per-iteration [BE, HF] temporaries and the double-buffered [BN, HF]
# window blocks scale with HF against the v5e's 16 MB scoped-VMEM budget.
# Measured on the v5e: hf=768 (34.6 ms/step) and hf=1020 (49.3 ms/step)
# compile and run at BE=256; hf=1536 (h256 x 6 heads) OOMs at BE=512 AND
# at BE=128 (the backward's seven double-buffered [BN, HF] node windows
# alone approach the budget).  Wider configs stay fused by TILING over the
# flat head-feature axis (:func:`gat_edge_attention_tiled`): attention is
# independent per head, so the heads split into balanced groups of
# group_hf <= this limit, one kernel call each.  Only a SINGLE head wider
# than the limit (f > FUSED_HF_LIMIT) still forces the composed path.
FUSED_HF_LIMIT = 1024


def _edge_block(hf: int) -> int:
    """Edge-block size that keeps the kernels' [BE, HF]-scale temporaries
    (4-5 live per iteration, f32) inside scoped VMEM alongside the
    double-buffered [BN, HF] node windows (hf=768 -> BE=256 measured
    34.6 ms/step at the h128 sweep config, vs 36.1 at BE=512)."""
    return _EDGE_BLOCK if hf <= 512 else 256


# sentinels deliberately 1e9, NOT 1e30: they ride one-hot MATMULS (m_e =
# onehot @ m), and reduced-precision matmul backends (CPU oneDNN tf32-ish
# rounding; MXU bf16 passes) round huge magnitudes with absolute errors
# that can flip exp(e - m_e) into overflow -> inf * 0 = NaN.  At 1e9 the
# worst rounding error (~5e-4 relative = 5e5) still leaves exp(-1e9 +
# 5e5) == 0 exactly.
_NEG = -1e9
_POS = 1e9
_HP = 128  # head-axis lane padding (H <= 128)


def _window_maps(n_blocks):
    """GAT-shaped view of the builder's shared index maps: the ±1 window
    unrolled to named slots (the attention kernels address window blocks
    individually rather than as a spec-generated list)."""
    eix, xoff, const, _outx = _shared_window_maps(n_blocks)
    return eix, xoff(-1), xoff(0), xoff(1), const


def _head_expander(hf: int, f: int):
    """[Hp, HF] 0/1 matrix: lane l of the output belongs to head l // f."""
    head = jax.lax.broadcasted_iota(jnp.int32, (_HP, hf), 1) // f
    row = jax.lax.broadcasted_iota(jnp.int32, (_HP, hf), 0)
    return (head == row).astype(jnp.float32)


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------


def _make_fwd_kernel(slope: float, f: int, h: int):
    from jax.experimental import pallas as pl

    def kernel(si_ref, se_ref, av_ref, fi_ref,
               send_ref, recv_ref, mask_ref, b_ref, am_ref,
               xlm1_ref, xl0_ref, xlp1_ref, xr0_ref,
               acc_ref, m_ref, d_ref):
        s = pl.program_id(0)
        i = si_ref[s]

        @pl.when(fi_ref[s] == 1)
        def _init():
            acc_ref[:] = jnp.zeros_like(acc_ref)
            # garbage head lanes (>= h) pin to 0 so their p stays exp(0)=1
            # (finite) — they are sliced away on the host side
            lane = jax.lax.broadcasted_iota(jnp.int32, m_ref.shape, 1)
            m_ref[:] = jnp.where(lane < h, _NEG, 0.0)
            d_ref[:] = jnp.zeros_like(d_ref)

        @pl.when(av_ref[s] == 1)
        def _acc():
            bn = acc_ref.shape[0]
            be = send_ref.shape[0]
            hf = acc_ref.shape[1]
            base = (i - 1) * bn
            sloc = send_ref[:] - base
            onehot_s = (sloc == jax.lax.broadcasted_iota(
                jnp.int32, (be, 3 * bn), 1)).astype(jnp.float32)
            xcat = jnp.concatenate(
                [xlm1_ref[:], xl0_ref[:], xlp1_ref[:]],
                axis=0).astype(jnp.float32)
            xs = jax.lax.dot_general(
                onehot_s, xcat, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [BE, HF]
            rloc = recv_ref[:] - i * bn
            onehot_r = (rloc == jax.lax.broadcasted_iota(
                jnp.int32, (be, bn), 1)).astype(jnp.float32)
            xt = jax.lax.dot_general(
                onehot_r, xr0_ref[:].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            zpre = xs + xt
            z = jnp.where(zpre > 0, zpre, slope * zpre)
            e = jax.lax.dot_general(
                z, am_ref[:].astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [BE, Hp]
            valid = (jnp.sum(onehot_r, axis=1, keepdims=True)
                     * mask_ref[:].astype(jnp.float32))
            e = jnp.where(valid > 0, e, _NEG)
            # per-head block max (static H loop keeps intermediates 2D —
            # a [BE, BN, Hp] masked-max blob would blow VMEM)
            m_blk = m_ref[:]
            lane_n = jax.lax.broadcasted_iota(
                jnp.int32, (bn, m_blk.shape[1]), 1)
            bm = jnp.zeros_like(m_blk)
            for hh in range(h):
                masked = jnp.where(
                    onehot_r > 0, e[:, hh][:, None], _NEG)  # [BE, BN]
                bm_h = jnp.max(masked, axis=0)              # [BN]
                bm = jnp.where(lane_n == hh, bm_h[:, None], bm)
            m_new = jnp.maximum(m_blk, bm)
            r = jnp.exp(m_blk - m_new)                      # [BN, Hp]
            m_e = jax.lax.dot_general(
                onehot_r, m_new, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            p = jnp.exp(e - m_e) * valid                    # [BE, Hp]
            d_ref[:] = d_ref[:] * r + jax.lax.dot_general(
                onehot_r, p, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            ex = _head_expander(hf, f)                      # [Hp, HF]
            pb_x = jax.lax.dot_general(
                p * b_ref[:].astype(jnp.float32), ex,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [BE, HF]
            r_x = jax.lax.dot_general(
                r, ex, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [BN, HF]
            acc_ref[:] = acc_ref[:] * r_x + jax.lax.dot_general(
                onehot_r, xs * pb_x, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[:] = m_new

    return kernel


def _pad_nodes(x, n_pad):
    n = x.shape[0]
    return jnp.zeros((n_pad,) + x.shape[1:], jnp.float32).at[:n].set(
        x.astype(jnp.float32))


def _pad_edges(senders, receivers, edge_mask, b_edge, n_pad, e_pad):
    e = senders.shape[0]
    send_p = jnp.full((e_pad, 1), n_pad, jnp.int32).at[:e, 0].set(
        senders.astype(jnp.int32))
    recv_p = jnp.full((e_pad, 1), n_pad, jnp.int32).at[:e, 0].set(
        receivers.astype(jnp.int32))
    # collate parks padding edges on REAL node N-1 — they must not enter
    # any node's max/denominator, so the mask is an explicit kernel input
    # (a zero dropout bit is NOT equivalent: dropped real edges still
    # count in the denominator)
    mask_p = jnp.zeros((e_pad, 1), jnp.float32).at[:e, 0].set(
        edge_mask.astype(jnp.float32))
    b_p = jnp.zeros((e_pad, _HP), jnp.float32).at[:e, :b_edge.shape[1]].set(
        b_edge.astype(jnp.float32))
    return send_p, recv_p, mask_p, b_p


def _fwd_impl(xl, xr, att_mat, senders, receivers, edge_mask, b_edge,
              slope, f, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, hf = xl.shape
    h = att_mat.shape[1]
    bn, be = _NODE_BLOCK, _edge_block(hf)
    n_pad = _round_up(n, bn)
    e_pad = _round_up(max(senders.shape[0], 1), be)
    xl_p = _pad_nodes(xl, n_pad)
    xr_p = _pad_nodes(xr, n_pad)
    send_p, recv_p, mask_p, b_p = _pad_edges(
        senders, receivers, edge_mask, b_edge, n_pad, e_pad)
    am_p = jnp.zeros((hf, _HP), jnp.float32).at[:, :h].set(
        att_mat.astype(jnp.float32))
    n_blocks, n_eblocks = n_pad // bn, e_pad // be

    step_i, step_eb, acc_valid, is_first, s_max = _dense_schedule(
        recv_p[:, 0], n_blocks, bn, be, n_eblocks)
    eix, xm1, x0, xp1, const = _window_maps(n_blocks)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s_max,),
        in_specs=[
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, _HP), eix),
            pl.BlockSpec((hf, _HP), const),
            pl.BlockSpec((bn, hf), xm1),
            pl.BlockSpec((bn, hf), x0),
            pl.BlockSpec((bn, hf), xp1),
            pl.BlockSpec((bn, hf), x0),
        ],
        out_specs=[
            pl.BlockSpec((bn, hf), lambda s, si, se, av, fi: (si[s], 0)),
            pl.BlockSpec((bn, _HP), lambda s, si, se, av, fi: (si[s], 0)),
            pl.BlockSpec((bn, _HP), lambda s, si, se, av, fi: (si[s], 0)),
        ],
    )
    acc, m, d = pl.pallas_call(
        _make_fwd_kernel(slope, f, h),
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, hf), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, _HP), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, _HP), jnp.float32),
        ],
        grid_spec=grid_spec,
        interpret=interpret,
        name="gat_attn_fwd",
    )(step_i, step_eb, acc_valid, is_first,
      send_p, recv_p, mask_p, b_p, am_p, xl_p, xl_p, xl_p, xr_p)
    return acc[:n], m[:n, :h], d[:n, :h]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------


def _make_bwd_r_kernel(slope: float, f: int):
    """Receiver-sorted pass: dxr (block rows) + datt_mat (accumulated)."""
    from jax.experimental import pallas as pl

    def kernel(si_ref, se_ref, av_ref, fi_ref,
               send_ref, recv_ref, mask_ref, b_ref, am_ref, qm_ref,
               xlm1_ref, xl0_ref, xlp1_ref, xr0_ref, ga0_ref, mg0_ref,
               dxr_ref, datt_ref):
        s = pl.program_id(0)
        i = si_ref[s]

        @pl.when(fi_ref[s] == 1)
        def _init():
            dxr_ref[:] = jnp.zeros_like(dxr_ref)

        @pl.when(s == 0)
        def _init_att():
            datt_ref[:] = jnp.zeros_like(datt_ref)

        @pl.when(av_ref[s] == 1)
        def _acc():
            bn = dxr_ref.shape[0]
            be = send_ref.shape[0]
            hf = dxr_ref.shape[1]
            base = (i - 1) * bn
            sloc = send_ref[:] - base
            onehot_s = (sloc == jax.lax.broadcasted_iota(
                jnp.int32, (be, 3 * bn), 1)).astype(jnp.float32)
            xcat = jnp.concatenate(
                [xlm1_ref[:], xl0_ref[:], xlp1_ref[:]],
                axis=0).astype(jnp.float32)
            xs = jax.lax.dot_general(
                onehot_s, xcat, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            rloc = recv_ref[:] - i * bn
            onehot_r = (rloc == jax.lax.broadcasted_iota(
                jnp.int32, (be, bn), 1)).astype(jnp.float32)
            xt = jax.lax.dot_general(
                onehot_r, xr0_ref[:].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            zpre = xs + xt
            z = jnp.where(zpre > 0, zpre, slope * zpre)
            e = jax.lax.dot_general(
                z, am_ref[:].astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            valid = (jnp.sum(onehot_r, axis=1, keepdims=True)
                     * mask_ref[:].astype(jnp.float32))
            ga_e = jax.lax.dot_general(
                onehot_r, ga0_ref[:].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            mg = mg0_ref[:].astype(jnp.float32)            # [BN, 2*Hp]
            m_e = jax.lax.dot_general(
                onehot_r, mg[:, :_HP], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            # rows with no one-hot (other-block/padding edges) get m_e = 0
            # while e = -1e30 -> p = 0; real rows read the true m
            gd_e = jax.lax.dot_general(
                onehot_r, mg[:, _HP:], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            e = jnp.where(valid > 0, e, _NEG)
            p = jnp.exp(e - m_e) * valid
            q = jax.lax.dot_general(
                xs * ga_e, qm_ref[:].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [BE, Hp]
            de = p * (b_ref[:].astype(jnp.float32) * q + gd_e)
            dz = jax.lax.dot_general(
                de, am_ref[:].astype(jnp.float32),
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # [BE, HF]
            dz = dz * jnp.where(zpre > 0, 1.0, slope)
            dxr_ref[:] += jax.lax.dot_general(
                onehot_r, dz, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            datt_ref[:] += jax.lax.dot_general(
                z, de, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [HF, Hp]

    return kernel


def _make_bwd_s_kernel(slope: float, f: int):
    """Sender-sorted pass: dxl rows = sum_e (p b ga[r] + dz)."""
    from jax.experimental import pallas as pl

    def kernel(si_ref, se_ref, av_ref, fi_ref,
               send_ref, recv_ref, mask_ref, b_ref, am_ref, qm_ref,
               xl0_ref, xrm1_ref, xr0_ref, xrp1_ref,
               gam1_ref, ga0_ref, gap1_ref, mgm1_ref, mg0_ref, mgp1_ref,
               dxl_ref):
        s = pl.program_id(0)
        i = si_ref[s]

        @pl.when(fi_ref[s] == 1)
        def _init():
            dxl_ref[:] = jnp.zeros_like(dxl_ref)

        @pl.when(av_ref[s] == 1)
        def _acc():
            bn = dxl_ref.shape[0]
            be = send_ref.shape[0]
            hf = dxl_ref.shape[1]
            # sorted side: SENDERS in block i
            sloc = send_ref[:] - i * bn
            onehot_s = (sloc == jax.lax.broadcasted_iota(
                jnp.int32, (be, bn), 1)).astype(jnp.float32)
            base = (i - 1) * bn
            rloc = recv_ref[:] - base
            onehot_r = (rloc == jax.lax.broadcasted_iota(
                jnp.int32, (be, 3 * bn), 1)).astype(jnp.float32)
            xs = jax.lax.dot_general(
                onehot_s, xl0_ref[:].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            xrcat = jnp.concatenate(
                [xrm1_ref[:], xr0_ref[:], xrp1_ref[:]],
                axis=0).astype(jnp.float32)
            xt = jax.lax.dot_general(
                onehot_r, xrcat, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            gacat = jnp.concatenate(
                [gam1_ref[:], ga0_ref[:], gap1_ref[:]],
                axis=0).astype(jnp.float32)
            ga_e = jax.lax.dot_general(
                onehot_r, gacat, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            mgcat = jnp.concatenate(
                [mgm1_ref[:], mg0_ref[:], mgp1_ref[:]],
                axis=0).astype(jnp.float32)
            mg_e = jax.lax.dot_general(
                onehot_r, mgcat, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)        # [BE, 2Hp]
            m_e = mg_e[:, :_HP]
            gd_e = mg_e[:, _HP:]
            zpre = xs + xt
            z = jnp.where(zpre > 0, zpre, slope * zpre)
            e = jax.lax.dot_general(
                z, am_ref[:].astype(jnp.float32), (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            valid = (jnp.sum(onehot_s, axis=1, keepdims=True)
                     * mask_ref[:].astype(jnp.float32))
            e = jnp.where(valid > 0, e, _NEG)
            p = jnp.exp(e - m_e) * valid
            q = jax.lax.dot_general(
                xs * ga_e, qm_ref[:].astype(jnp.float32),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            b = b_ref[:].astype(jnp.float32)
            de = p * (b * q + gd_e)
            dz = jax.lax.dot_general(
                de, am_ref[:].astype(jnp.float32), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dz = dz * jnp.where(zpre > 0, 1.0, slope)
            ex = _head_expander(hf, f)
            pb_x = jax.lax.dot_general(
                p * b, ex, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            contrib = pb_x * ga_e + dz
            dxl_ref[:] += jax.lax.dot_general(
                onehot_s, contrib, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    return kernel


# ---------------------------------------------------------------------------
# public custom-vjp op
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(8,))
def gat_edge_attention(xl, xr, att_mat, senders, receivers, sender_perm,
                       edge_mask, b_edge, slope_f):
    """Edge-side GATv2 attention partials.

    Returns (acc [N, HF], m [N, H], d [N, H]) where, over each node's REAL
    incident edges: m = max logit, d = sum exp(e - m),
    acc = sum exp(e - m) * b * xl[src].  The caller merges the self-loop
    and normalizes — and MUST ``stop_gradient`` the m it uses (softmax
    shift-invariance makes that exact; this op's backward treats m as a
    constant and returns a zero cotangent through it).

    ``att_mat`` [HF, H]: block-diagonal logit matrix (att[h, f] at row
    h*F+f, column h) — build it with jnp ops from the [H, F] parameter so
    autodiff carries datt_mat back to it.
    ``b_edge`` [E, H]: edge_mask times dropout-bits/keep (ones for eval).
    ``slope_f``: static (negative_slope, per-head F) pair.
    Differentiable wrt xl, xr, att_mat.
    """
    slope, f = slope_f
    interpret = jax.default_backend() != "tpu"
    return _fwd_impl(xl, xr, att_mat, senders, receivers, edge_mask, b_edge,
                     slope, f, interpret)


def _gea_fwd(xl, xr, att_mat, senders, receivers, sender_perm, edge_mask,
             b_edge, slope_f):
    out = gat_edge_attention(xl, xr, att_mat, senders, receivers,
                             sender_perm, edge_mask, b_edge, slope_f)
    _, m, _ = out
    return out, (xl, xr, att_mat, senders, receivers, sender_perm,
                 edge_mask, b_edge, m)


def _gea_bwd(slope_f, res, cot):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    slope, f = slope_f
    xl, xr, att_mat, senders, receivers, sender_perm, edge_mask, b_edge, m \
        = res
    ga, _gm, gd = cot  # gm is zero by contract (caller stop_gradients m)
    interpret = jax.default_backend() != "tpu"

    n, hf = xl.shape
    h = att_mat.shape[1]
    bn, be = _NODE_BLOCK, _edge_block(hf)
    n_pad = _round_up(n, bn)
    e_pad = _round_up(max(senders.shape[0], 1), be)
    xl_p = _pad_nodes(xl, n_pad)
    xr_p = _pad_nodes(xr, n_pad)
    send_p, recv_p, mask_p, b_p = _pad_edges(
        senders, receivers, edge_mask, b_edge, n_pad, e_pad)
    am_p = jnp.zeros((hf, _HP), jnp.float32).at[:, :h].set(
        att_mat.astype(jnp.float32))
    rows = jnp.arange(hf)
    qm_p = jnp.zeros((hf, _HP), jnp.float32).at[rows, rows // f].set(1.0)
    ga_p = _pad_nodes(ga, n_pad)
    # m and gd ride one concatenated [N, 2*Hp] array; the m half fills
    # padding rows/lanes with +BIG so their p = exp(e - BIG) underflows to
    # zero instead of overflowing to inf*0 = NaN
    mg = jnp.full((n_pad, 2 * _HP), _POS, jnp.float32)
    mg = mg.at[:n, :h].set(m.astype(jnp.float32))
    mg = mg.at[:, _HP:].set(0.0)
    mg = mg.at[:n, _HP:_HP + h].set(gd.astype(jnp.float32))
    n_blocks, n_eblocks = n_pad // bn, e_pad // be
    eix, xm1, x0, xp1, const = _window_maps(n_blocks)

    # ---- pass R: receiver-sorted (the natural edge order) ----
    step_i, step_eb, acc_valid, is_first, s_max = _dense_schedule(
        recv_p[:, 0], n_blocks, bn, be, n_eblocks)
    grid_r = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s_max,),
        in_specs=[
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, _HP), eix),
            pl.BlockSpec((hf, _HP), const),
            pl.BlockSpec((hf, _HP), const),
            pl.BlockSpec((bn, hf), xm1),
            pl.BlockSpec((bn, hf), x0),
            pl.BlockSpec((bn, hf), xp1),
            pl.BlockSpec((bn, hf), x0),
            pl.BlockSpec((bn, hf), x0),
            pl.BlockSpec((bn, 2 * _HP), x0),
        ],
        out_specs=[
            pl.BlockSpec((bn, hf), lambda s, si, se, av, fi: (si[s], 0)),
            pl.BlockSpec((hf, _HP), const),
        ],
    )
    dxr, datt = pl.pallas_call(
        _make_bwd_r_kernel(slope, f),
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, hf), jnp.float32),
            jax.ShapeDtypeStruct((hf, _HP), jnp.float32),
        ],
        grid_spec=grid_r,
        interpret=interpret,
        name="gat_attn_bwd_r",
    )(step_i, step_eb, acc_valid, is_first,
      send_p, recv_p, mask_p, b_p, am_p, qm_p,
      xl_p, xl_p, xl_p, xr_p, ga_p, mg)

    # ---- pass S: sender-sorted (via the host-precomputed permutation) ----
    if sender_perm is None:
        sender_perm = jnp.argsort(senders, stable=True)
    perm = sender_perm.astype(jnp.int32)
    e_n = senders.shape[0]
    send_s = jnp.full((e_pad, 1), n_pad, jnp.int32).at[:e_n, 0].set(
        senders[perm].astype(jnp.int32))
    recv_s = jnp.full((e_pad, 1), n_pad, jnp.int32).at[:e_n, 0].set(
        receivers[perm].astype(jnp.int32))
    b_s = jnp.zeros((e_pad, _HP), jnp.float32).at[:e_n, :b_edge.shape[1]].set(
        b_edge[perm].astype(jnp.float32))
    mask_s = jnp.zeros((e_pad, 1), jnp.float32).at[:e_n, 0].set(
        edge_mask[perm].astype(jnp.float32))
    step_i2, step_eb2, acc_valid2, is_first2, s_max2 = _dense_schedule(
        send_s[:, 0], n_blocks, bn, be, n_eblocks)
    grid_s = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(s_max2,),
        in_specs=[
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, 1), eix),
            pl.BlockSpec((be, _HP), eix),
            pl.BlockSpec((hf, _HP), const),
            pl.BlockSpec((hf, _HP), const),
            pl.BlockSpec((bn, hf), x0),       # xl block (sender side)
            pl.BlockSpec((bn, hf), xm1),      # xr windows
            pl.BlockSpec((bn, hf), x0),
            pl.BlockSpec((bn, hf), xp1),
            pl.BlockSpec((bn, hf), xm1),      # ga windows
            pl.BlockSpec((bn, hf), x0),
            pl.BlockSpec((bn, hf), xp1),
            pl.BlockSpec((bn, 2 * _HP), xm1),  # mg windows
            pl.BlockSpec((bn, 2 * _HP), x0),
            pl.BlockSpec((bn, 2 * _HP), xp1),
        ],
        out_specs=pl.BlockSpec(
            (bn, hf), lambda s, si, se, av, fi: (si[s], 0)),
    )
    dxl = pl.pallas_call(
        _make_bwd_s_kernel(slope, f),
        out_shape=jax.ShapeDtypeStruct((n_pad, hf), jnp.float32),
        grid_spec=grid_s,
        interpret=interpret,
        name="gat_attn_bwd_s",
    )(step_i2, step_eb2, acc_valid2, is_first2,
      send_s, recv_s, mask_s, b_s, am_p, qm_p,
      xl_p, xr_p, xr_p, xr_p, ga_p, ga_p, ga_p, mg, mg, mg)

    return (dxl[:n].astype(xl.dtype), dxr[:n].astype(xr.dtype),
            datt[:, :h].astype(att_mat.dtype), None, None, None,
            jnp.zeros_like(edge_mask), jnp.zeros_like(b_edge))


gat_edge_attention.defvjp(_gea_fwd, _gea_bwd)


def fused_head_width_ok(f: int) -> bool:
    """The per-head width gate, reading THIS module's live limit — the
    dispatcher (models/gat.py) queries it instead of caching an
    import-time copy, so adjusting FUSED_HF_LIMIT at runtime (tests,
    smaller-VMEM parts) moves the gate and the tiling together."""
    return f <= FUSED_HF_LIMIT


def _head_groups(h: int, f: int):
    """Balanced head-group sizes with group_hf = size * f <= FUSED_HF_LIMIT.

    Groups are as equal as possible (6 heads at cap 4 -> [3, 3], not
    [4, 2]) so same-shaped calls share one compiled kernel."""
    assert f <= FUSED_HF_LIMIT, "single head exceeds the kernel width cap"
    gmax = max(1, FUSED_HF_LIMIT // f)
    n_groups = -(-h // gmax)
    base, rem = divmod(h, n_groups)
    return [base + 1] * rem + [base] * (n_groups - rem)


def gat_edge_attention_tiled(xl, xr, att_mat, senders, receivers,
                             sender_perm, edge_mask, b_edge, slope_f):
    """:func:`gat_edge_attention`, tiled over the flat head-feature axis
    so hf = h*f > FUSED_HF_LIMIT configs (h256 x 6 heads = 1536, the
    round-4 VMEM OOM) STAY on the fused path instead of silently
    reverting to the composed segment ops.  Attention is independent per
    head, so the heads split into balanced groups of group_hf <= the
    limit — one kernel call per group over column slices of
    xl / xr / att_mat / b_edge, outputs concatenated back.  Gradients
    flow through the slicing (each group's custom VJP applies); the
    caller's stop_gradient(m) contract is unchanged.  Within the limit
    this is exactly one untiled call."""
    slope, f = slope_f
    h = att_mat.shape[1]
    if h * f <= FUSED_HF_LIMIT:
        return gat_edge_attention(xl, xr, att_mat, senders, receivers,
                                  sender_perm, edge_mask, b_edge, slope_f)
    accs, ms, ds = [], [], []
    h0 = 0
    for size in _head_groups(h, f):
        h1 = h0 + size
        cols = slice(h0 * f, h1 * f)
        acc, m, d = gat_edge_attention(
            xl[:, cols], xr[:, cols], att_mat[cols, h0:h1], senders,
            receivers, sender_perm, edge_mask, b_edge[:, h0:h1], slope_f)
        accs.append(acc)
        ms.append(m)
        ds.append(d)
        h0 = h1
    return (jnp.concatenate(accs, axis=1), jnp.concatenate(ms, axis=1),
            jnp.concatenate(ds, axis=1))
