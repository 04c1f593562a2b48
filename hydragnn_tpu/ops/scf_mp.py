"""SchNet CFConv's filter network as the CHAIN of the gather-multiply
kernels (:mod:`hydragnn_tpu.ops.fused_mp`, the multiplier's third source):
filter-MLP -> gather -> multiply -> segment sum in ONE Pallas pass forward
and ONE backward — no [E, F] HBM stream either way.

  filt_e = (ssp(rbf_e @ W0 + b0) @ W1 + b1) * cm_e
  out[n] = sum_{e: recv[e]=n} h[send_e] * filt_e

This module is the packing front: the geometry stream carries the rbf
lanes, the cutoff*mask ``cm`` on lane G and a constant-1 bias lane last
(b0 folded onto W0's matching row) — so db0 and dcm fall out of the
weight-block and geometry cotangents with no special-casing, and every
pack is a ``lax.pad`` / concatenate whose transpose is a slice.  The
kernels, their schedule and the one-pass VJP (``dw`` pulled back through
the chain in VMEM; a ``dgeo`` stream only when the geometry is
differentiated) live in fused_mp.  Until PR 27 this was a spec on the
three-pass builder (``scf_fwd`` / ``scf_bwd_p`` / ``scf_bwd_s``, fused_block.py),
gated to >= 256 filters; every width now runs the chain form (PERF.md,
PR 27).

Width limits: G + 2 geometry lanes within the padded tile(s) and
F <= SCF_F_LIMIT (VMEM: W1 and its grad accumulator are [F, F] blocks).
Callers gate on both and fall back to the composed filter.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp

from hydragnn_tpu.ops.aggregate import _round_up
from hydragnn_tpu.ops.fused_block import _GP, _dot, _ssp
from hydragnn_tpu.ops.fused_mp import _EDGE_BLOCK, gather_chain_segment_sum

SCF_F_LIMIT = 1024
SCF_G_LIMIT = 127


@functools.lru_cache(maxsize=None)
def _make_chain(g: int):
    """The per-edge-block filter network (static per basis size, cached so
    the kernels' static argument is one object per ``g``)."""
    def chain(w_vals, geo, dt):
        w0, w1, b1 = w_vals
        t0 = _dot(geo, w0, ((1,), (0,)), dt)
        f2 = _dot(_ssp(t0), w1, ((1,), (0,)), dt) + b1[0:1, :]
        return f2 * geo[:, g:g + 1]        # cm rides geometry lane G
    return chain


def _pack_geo(rbf, cm):
    """``[E_pad, GPW]`` f32: rbf lanes, ``cm`` on lane G, zeros, the
    constant-1 bias lane last.  One ``lax.pad`` plus a broadcast constant:
    the transpose is a slice, never an [E, F] gather.  (Measured on the
    v5e, PR 27: concatenate -> pad -> add costs 0.85 ms a step at the
    resident cell's shapes; a sum of two lane-offset pads, which XLA does
    write in one fusion, costs 1.11 and slows the eval programs too.)"""
    e, g = rbf.shape
    gpw = _round_up(g + 2, _GP)
    geo = jnp.concatenate(
        [rbf, cm[:, None].astype(rbf.dtype)], axis=1).astype(jnp.float32)
    geo_p = jnp.pad(geo, ((0, _round_up(max(e, 1), _EDGE_BLOCK) - e),
                          (0, gpw - g - 1)))
    return geo_p + (jnp.arange(gpw) == gpw - 1).astype(jnp.float32)


def scf_edge_pipeline(h, rbf, cm, em, w0, b0, w1, b1, senders, receivers,
                      sender_perm=None):
    """``out[n] = sum_{e: recv[e]=n} h[send[e]] * filt_e`` with
    ``filt_e = (ssp(rbf_e @ w0 + b0) @ w1 + b1) * cm_e`` computed in-VMEM.

    Differentiable wrt h, rbf, cm, w0, b0, w1, b1; ``drbf`` / ``dcm`` are
    computed (one per-edge output stream) only when rbf / cm are
    themselves differentiated — positions, i.e. force training.  Requires
    the collate invariants of :func:`fused_mp.gather_mul_segment_sum` plus
    G <= SCF_G_LIMIT and F <= SCF_F_LIMIT (callers gate).
    ``cm`` must be zero on padding edges (it carries the edge mask).
    ``em`` is the int32 edge-validity mask (1 = real): em == 0 edges are
    skipped by the block schedule entirely, halving the scheduled MXU
    work at flagship padding ratios.  Contract: em == 0 edges carry
    cm == 0, sort after all real edges (collate guarantees this), and get
    EXACTLY ZERO for every grad — including dcm, whose true value at
    cm == 0 need not be zero; callers must not consume dcm on masked
    edges (SchNet's hard-zeroed cutoff `where` satisfies this).
    ``sender_perm`` is unread (the kernels run on the edge list as
    shipped); the argument stays for the callers that pass it.

    Numerics under a bf16 model (``h`` bf16): the filter MLP and its
    backward matmuls — the dW0 / dW1 weight grads and drbf included — run
    with bf16 operands and f32 accumulation, where the composed filter
    runs in f32; drift is pinned to < 4 % of grad scale by
    tests/test_scf_fused.py::test_bf16_gradients_within_tolerance."""
    del sender_perm
    f = h.shape[1]
    g = rbf.shape[1]
    f_pad = _round_up(max(f, 1), 128)
    gpw = _round_up(g + 2, _GP)
    f32 = jnp.float32
    w0_p = jnp.concatenate(
        [jnp.pad(w0.astype(f32), ((0, gpw - 1 - g), (0, f_pad - f))),
         jnp.pad(b0.astype(f32), (0, f_pad - f))[None, :]], axis=0)
    w1_p = jnp.pad(w1.astype(f32), ((0, f_pad - f), (0, f_pad - f)))
    b1_p = jnp.broadcast_to(
        jnp.pad(b1.astype(f32), (0, f_pad - f)), (8, f_pad))
    if h.dtype == jnp.bfloat16:
        # halves the constant weight blocks' VMEM; bias stays f32 (added
        # after the f32-accumulating dots)
        w0_p = w0_p.astype(jnp.bfloat16)
        w1_p = w1_p.astype(jnp.bfloat16)
    return gather_chain_segment_sum(
        h, _pack_geo(rbf, cm), (w0_p, w1_p, b1_p), _make_chain(int(g)),
        senders, receivers, edge_valid=em)
