"""DimeNet++ stack (parity: reference hydragnn/models/DIMEStack.py).

Directional message passing on *edge* features with triplet (k->j->i)
interactions.  The reference builds ragged triplet indices per batch with
torch_sparse SparseTensor (DIMEStack.py:158-182); here the triplet table is
precomputed host-side by the batcher into padded static arrays
(:func:`build_triplets` / :func:`add_dimenet_extras`), and distances/angles
are recomputed on device from positions (keeping ``jax.grad`` w.r.t.
positions intact for force losses).

The Bessel radial basis and the spherical (Legendre x spherical-Bessel)
basis are evaluated in pure JAX; spherical-Bessel zeros are found host-side
with scipy at module-construction time and cached.
"""

from __future__ import annotations

import functools
import math
from typing import Tuple

import numpy as np
import jax
import jax.numpy as jnp
import flax.linen as nn

from hydragnn_tpu.graph import segment
from hydragnn_tpu.graph.batch import GraphBatch
from hydragnn_tpu.models.base import Base


# ---------------------------------------------------------------------------
# host-side: triplet construction + spherical-Bessel zeros
# ---------------------------------------------------------------------------


def build_triplets(edge_index: np.ndarray, num_nodes: int):
    """Triplet table (k->j->i) from an edge list (parity with reference
    triplets(), DIMEStack.py:158-182).

    For every pair of edges (k->j) and (j->i) with k != i, emits node indices
    (idx_i, idx_j, idx_k) and the two edge ids (idx_kj, idx_ji), with idx_ji
    nondecreasing (the dense sorted-scatter in InteractionPPBlock relies on
    this; enforced by :func:`add_dimenet_extras`).

    Fully vectorized (numpy): group incoming edge ids by destination node,
    then expand each edge (j->i) against the incoming-edge group of j via
    repeat + cumsum arithmetic — no per-edge Python loop (round-2 VERDICT
    flagged the loop builder as the DimeNet input bottleneck).
    """
    src = np.asarray(edge_index[0], np.int64)
    dst = np.asarray(edge_index[1], np.int64)  # j->i: src=j, dst=i
    e = src.shape[0]
    if e == 0:
        return tuple(np.zeros((0,), np.int32) for _ in range(5))
    # incoming edge ids per node, grouped: stable argsort of dst keeps edge
    # ids increasing within each group (matches the loop builder's order)
    order = np.argsort(dst, kind="stable")
    counts = np.bincount(dst, minlength=num_nodes)
    ptr = np.zeros(num_nodes + 1, np.int64)
    np.cumsum(counts, out=ptr[1:])
    # edge eid (j->i) pairs with every incoming edge of j
    num = counts[src]  # candidates per edge
    ji = np.repeat(np.arange(e, dtype=np.int64), num)
    ends = np.cumsum(num)
    within = np.arange(int(ends[-1]), dtype=np.int64) - np.repeat(
        ends - num, num)
    kj = order[ptr[src[ji]] + within]
    keep = src[kj] != dst[ji]  # drop k == i backtracking triplets
    ji, kj = ji[keep], kj[keep]
    return tuple(
        a.astype(np.int32) for a in (dst[ji], src[ji], src[kj], kj, ji)
    )


class DnTriGate:
    """Per-dataset/loader gate for the factored-basis fused-triplet path.

    Marker PRESENCE ("dn_tri_ok") is the static gate the model reads, so it
    must be CONSISTENT across every batch of a run: DeviceStackLoader
    np.stacks consecutive batches' extras trees, and a per-batch decision
    that flips mid-epoch produces mismatched trees (the ADVICE
    marker-instability item).  Two modes:

    - static (``max_edges_per_graph`` given): the decision is made ONCE from
      the dataset-wide bound.  A graph's real edges are contiguous in
      edge-id space (collate invariant), so a graph with at most L edges
      spans at most ceil((L-1)/_NODE_BLOCK) edge blocks at worst alignment —
      no per-batch measurement at all.
    - sticky (no bound — one-shot callers): per-batch measurement, but the
      first over-span batch disables the marker for the REST OF THE RUN
      (clean whole-run fallback instead of a mid-run tree flip; batches
      already emitted keep their marker, so prefer the static mode for any
      multi-batch pipeline).
    """

    def __init__(self, max_edges_per_graph=None):
        from hydragnn_tpu.ops.fused_mp import _NODE_BLOCK

        self.static = max_edges_per_graph is not None
        if self.static:
            L = max(int(max_edges_per_graph), 1)
            self.span_bound = -(-(L - 1) // _NODE_BLOCK)
            self.ok = self.span_bound <= 2
        else:
            self.span_bound = None
            self.ok = True

    def allow(self, measure_span) -> bool:
        """``measure_span`` is a thunk (only called when a measurement is
        actually needed — the static mode never pays it)."""
        if self.static or not self.ok:
            return self.ok
        if measure_span() > 2:
            self.ok = False  # sticky: whole-run fallback from here on
            # one-shot: surfaces as the unified `fused_fallback` health
            # event ({arch, reason}) after the first epoch's dispatch
            from hydragnn_tpu.ops.fused_block import note_fallback

            note_fallback("DimeNet", reason="edge_span")
        return self.ok


def add_dimenet_extras(batch, max_triplets: int, tri_gate=None):
    """Post-collate hook: attach padded triplet arrays to a numpy GraphBatch.

    Padded triplets point at the trailing padded node/edge and carry mask 0.
    ``tri_gate`` (a :class:`DnTriGate`) decides the fused-triplet marker
    once per dataset/loader; omitted, a transient per-batch gate preserves
    the one-shot-caller behavior.
    """
    n, e = batch.x.shape[0], batch.senders.shape[0]
    ei = np.stack([np.asarray(batch.senders), np.asarray(batch.receivers)])
    # only real edges participate
    real = np.asarray(batch.edge_mask) > 0
    ei_real = ei[:, real]
    real_ids = np.nonzero(real)[0].astype(np.int32)
    ti, tj, tk, tkj, tji = build_triplets(ei_real, n)
    t = ti.shape[0]
    if t > max_triplets:
        raise ValueError(f"batch has {t} triplets > max_triplets={max_triplets}")

    def _pad(arr, fill):
        out = np.full((max_triplets,), fill, np.int32)
        out[:t] = arr
        return out

    # the dense sorted scatter over idx_ji (InteractionPPBlock) requires a
    # nondecreasing segment id sequence — enforce the invariant where it is
    # created so a future builder change cannot silently corrupt the scatter
    # (real_ids is increasing, so the mapped ids inherit tji's order, and
    # the e-1 padding fill keeps the full padded array nondecreasing too)
    if t and not np.all(np.diff(tji) >= 0):
        raise AssertionError("build_triplets produced non-sorted idx_ji")

    extras = dict(batch.extras)
    extras["dn_idx_i"] = _pad(ti, n - 1)
    extras["dn_idx_j"] = _pad(tj, n - 1)
    extras["dn_idx_k"] = _pad(tk, n - 1)
    idx_kj = _pad(real_ids[tkj] if t else tkj, e - 1)
    extras["dn_idx_kj"] = idx_kj
    extras["dn_idx_ji"] = _pad(real_ids[tji] if t else tji, e - 1)
    # stable argsort of idx_kj: lets the triplet-side gathers
    # (x_kj[idx_kj], rbf[idx_kj]) ride the dense sorted-scatter kernel in
    # their BACKWARD (otherwise XLA scatter-adds 188k unsorted rows per
    # layer — measured as the dominant cost of the DimeNet step)
    extras["dn_perm_kj"] = np.argsort(idx_kj, kind="stable").astype(np.int32)
    mask = np.zeros((max_triplets,), np.float32)
    mask[:t] = 1.0
    extras["dn_triplet_mask"] = mask

    # fused-triplet window marker: the interaction's triplet contraction is
    # message passing in EDGE space (x_kj[idx_kj] * sbf scattered over
    # idx_ji) and can ride the W-window fused kernel when every graph's
    # edge-id span fits the window.  Encoded in the marker array's SHAPE
    # (static under jit): shape[0] == window.  Gated like collate's
    # edge_perm_sender: only under the fused backend.
    from hydragnn_tpu.ops.aggregate import aggr_backend

    # OPT-IN (HYDRAGNN_DIMENET_FUSED_TRI=1): measured SLOWER than the XLA
    # composed path on the v5e sweep config (61.9 vs 56.9 ms/step; larger
    # block variants 60.4-61.0) — the T->E schedule's output-block count
    # (E/128 blocks for only ~2.3 triplets/edge) pays more per-step
    # overhead than the fused gather+scatter saves.  Kept as a tested
    # capability for shapes with denser triplet fan-in.
    from hydragnn_tpu.utils.env import env_flag

    if aggr_backend() == "fused":
        from hydragnn_tpu.ops.fused_mp import _NODE_BLOCK

        def measure_span() -> int:
            # max edge-block span of any graph in THIS batch (a triplet-free
            # batch trivially fits any window)
            if not t:
                return 0
            gid_of_edge = np.asarray(batch.node_gid)[
                np.asarray(batch.receivers)[real]].astype(np.int64)
            blocks = (real_ids // _NODE_BLOCK).astype(np.int64)
            ng = int(gid_of_edge.max()) + 1
            lo = np.full(ng, np.iinfo(np.int64).max)
            hi = np.full(ng, -1)
            np.minimum.at(lo, gid_of_edge, blocks)
            np.maximum.at(hi, gid_of_edge, blocks)
            occ = hi >= 0
            return int((hi[occ] - lo[occ]).max()) if occ.any() else 0

        # factored-basis triplet kernel marker (ops/dn_tri.py, default-on
        # when applicable): every graph's edge-id span fits the 5-block
        # window.  The decision comes from the DnTriGate — static per
        # dataset when the caller provides the max-edges-per-graph bound
        # (loaders do: load_data.py), so every batch of a run carries the
        # same extras tree; a span this close to the window limit means the
        # kernel is inapplicable anyway — molecular batches sit far below.
        if tri_gate is None:
            tri_gate = DnTriGate()  # transient: per-batch (one-shot callers)
        if not env_flag("HYDRAGNN_DN_TRI_OFF") and tri_gate.allow(
                measure_span):
            extras["dn_tri_ok"] = np.zeros((1,), np.float32)
        if env_flag("HYDRAGNN_DIMENET_FUSED_TRI"):
            # legacy opt-in T->E fused path (measured slower; kept as a
            # tested capability) — the user opted in, so a batch whose
            # graphs exceed the window is an error, not a fallback
            span = measure_span()
            if span > 2:
                raise ValueError(
                    f"HYDRAGNN_DIMENET_FUSED_TRI: a graph spans {span} "
                    f"edge blocks (> 2); the 5-block window cannot cover "
                    f"it — unset the knob for this dataset")
            extras["dn_tri_window"] = np.zeros((5,), np.float32)
    return batch.replace(extras=extras)


def count_triplets(edge_index: np.ndarray, num_nodes: int) -> int:
    """Number of (k->j->i, k != i) triplets for sizing the static pad."""
    src, dst = edge_index[0], edge_index[1]
    in_deg = np.bincount(dst, minlength=num_nodes)
    # per edge j->i: one triplet per incoming edge of j, minus (i->j) if present
    total = int(in_deg[src].sum())
    pair = set(zip(src.tolist(), dst.tolist()))
    reverse = sum(1 for s, d in pair if (d, s) in pair)
    return total - reverse


@functools.lru_cache(maxsize=8)
def spherical_bessel_zeros(num_spherical: int, num_radial: int) -> np.ndarray:
    """First ``num_radial`` positive zeros of j_l, l = 0..num_spherical-1."""
    from scipy.optimize import brentq
    from scipy.special import spherical_jn

    zeros = np.zeros((num_spherical, num_radial))
    # j_0 zeros are n*pi; bracket higher-l zeros between consecutive j_{l-1} zeros
    grid = np.arange(1, num_radial + num_spherical + 2) * np.pi
    prev = grid.astype(np.float64)  # zeros of j_0
    zeros[0] = prev[:num_radial]
    for l in range(1, num_spherical):
        cur = []
        for a, b in zip(prev[:-1], prev[1:]):
            cur.append(brentq(lambda x: spherical_jn(l, x), a, b))
        prev = np.asarray(cur)
        zeros[l] = prev[:num_radial]
    return zeros


@functools.lru_cache(maxsize=8)
def sbf_normalizer(num_spherical: int, num_radial: int) -> np.ndarray:
    """DimeNet normalization sqrt(2) / |j_{l+1}(z_ln)| per (l, n)."""
    from scipy.special import spherical_jn

    z = spherical_bessel_zeros(num_spherical, num_radial)
    norm = np.zeros_like(z)
    for l in range(num_spherical):
        norm[l] = math.sqrt(2.0) / np.abs(spherical_jn(l + 1, z[l]))
    return norm


# ---------------------------------------------------------------------------
# device-side basis functions
# ---------------------------------------------------------------------------


def envelope(x, exponent: int):
    """DimeNet polynomial envelope u(x) with u(1)=u'(1)=u''(1)=0."""
    p = exponent + 1
    a = -(p + 1) * (p + 2) / 2.0
    b = p * (p + 2.0)
    c = -p * (p + 1) / 2.0
    xs = jnp.maximum(x, 1e-7)
    val = 1.0 / xs + a * xs ** (p - 1) + b * xs**p + c * xs ** (p + 1)
    return jnp.where(x < 1.0, val, 0.0)


def _spherical_jl(l_max: int, x):
    """j_0..j_lmax via upward recurrence with a small-x Taylor guard."""
    xs = jnp.maximum(x, 1e-7)
    out = []
    j0 = jnp.sin(xs) / xs
    out.append(j0)
    if l_max >= 1:
        j1 = jnp.sin(xs) / xs**2 - jnp.cos(xs) / xs
        out.append(j1)
        for l in range(1, l_max):
            out.append((2 * l + 1) / xs * out[l] - out[l - 1])
    # small-x: j_l(x) ~ x^l / (2l+1)!! * (1 - x^2/(2(2l+3)) + x^4/(8(2l+3)(2l+5)))
    small = x < 0.5
    res = []
    dfact = 1.0
    for l in range(l_max + 1):
        if l > 0:
            dfact *= 2 * l + 1
        taylor = (
            x**l
            / dfact
            * (1.0 - x**2 / (2.0 * (2 * l + 3)) + x**4 / (8.0 * (2 * l + 3) * (2 * l + 5)))
        )
        res.append(jnp.where(small, taylor, out[l]))
    return res


def _legendre(l_max: int, c):
    """P_0..P_lmax(c) via the stable three-term recurrence."""
    out = [jnp.ones_like(c)]
    if l_max >= 1:
        out.append(c)
        for l in range(1, l_max):
            out.append(((2 * l + 1) * c * out[l] - l * out[l - 1]) / (l + 1))
    return out


class BesselBasis(nn.Module):
    """Radial Bessel basis with trainable frequencies (PyG BesselBasisLayer)."""

    num_radial: int
    cutoff: float
    envelope_exponent: int

    @nn.compact
    def __call__(self, dist):
        freq = self.param(
            "freq",
            lambda key: jnp.arange(1, self.num_radial + 1, dtype=jnp.float32) * jnp.pi,
        )
        d = dist[:, None] / self.cutoff
        return envelope(d, self.envelope_exponent) * jnp.sin(freq * d)


def radial_sbf(dist_norm, num_spherical: int, num_radial: int,
               envelope_exponent: int):
    """Per-EDGE radial part of the spherical basis: [E, S, R] with
    norm * j_l(z_lr * d) * envelope(d) at slot (l, r)."""
    zeros = jnp.asarray(
        spherical_bessel_zeros(num_spherical, num_radial), jnp.float32
    )  # [S, R]
    norms = jnp.asarray(sbf_normalizer(num_spherical, num_radial), jnp.float32)

    x = dist_norm[:, None, None] * zeros[None, :, :]  # [E, S, R]
    jls = _spherical_jl(num_spherical - 1, x.reshape(-1))  # list of [E*S*R]
    e = dist_norm.shape[0]
    # slot l needs only order l: slice the diagonal directly instead of
    # stacking all orders into [E, S, S, R] and einsum-selecting (the
    # round-3 code's 7x-materialized intermediate)
    rbf = jnp.stack(
        [jls[l].reshape(e, num_spherical, num_radial)[:, l, :]
         for l in range(num_spherical)],
        axis=1)  # [E, S, R]
    rbf = rbf * norms[None, :, :]
    return rbf * envelope(dist_norm[:, None, None], envelope_exponent)


def angular_cbf(angle, num_spherical: int):
    """Per-TRIPLET angular part: [T, S] real-spherical-harmonic Legendre."""
    cos_a = jnp.cos(angle)
    pl = _legendre(num_spherical - 1, cos_a)
    return jnp.stack(
        [
            math.sqrt((2 * l + 1) / (4 * math.pi)) * pl[l]
            for l in range(num_spherical)
        ],
        axis=1,
    )


def spherical_basis_factors(dist_norm, angle, num_spherical: int,
                            num_radial: int, envelope_exponent: int):
    """The spherical basis FACTORED: sbf[t] = radial[idx_kj[t]] *
    expand(cbf[t]) with radial EDGE-space [E, S*R] and cbf TRIPLET-space
    [T, S] (the fused triplet kernel lane-expands the angular columns
    over their radial slots in-VMEM — the [T, S*R] stream never
    exists)."""
    radial = radial_sbf(
        dist_norm, num_spherical, num_radial, envelope_exponent)
    radial2 = radial.reshape(dist_norm.shape[0],
                             num_spherical * num_radial)
    cbf = angular_cbf(angle, num_spherical)       # [T, S]
    return radial2, cbf


def spherical_basis(
    dist_norm, angle, idx_kj, num_spherical: int, num_radial: int,
    envelope_exponent: int, perm_kj=None
):
    """[T, num_spherical*num_radial] spherical basis per triplet.

    ``perm_kj`` (host-precomputed stable argsort of ``idx_kj``) routes the
    edge->triplet gather's backward through the dense sorted scatter.
    """
    rbf2, cbf = spherical_basis_factors(
        dist_norm, angle, num_spherical, num_radial, envelope_exponent)
    if perm_kj is not None:
        rbf_t = segment.gather_perm(rbf2, idx_kj, perm_kj)
    else:
        rbf_t = rbf2[idx_kj]
    out = rbf_t.reshape(-1, num_spherical, num_radial) * cbf[:, :, None]
    return out.reshape(-1, num_spherical * num_radial)


# ---------------------------------------------------------------------------
# network blocks (PyG DimeNet++ block structure)
# ---------------------------------------------------------------------------

_silu = jax.nn.silu


class ResidualLayer(nn.Module):
    dim: int

    @nn.compact
    def __call__(self, x):
        h = _silu(nn.Dense(self.dim, name="lin1")(x))
        h = _silu(nn.Dense(self.dim, name="lin2")(h))
        return x + h


class _ResidualParams(nn.Module):
    """Parameters of a ResidualLayer WITHOUT its matmuls (same names
    lin1/lin2 with kernel/bias, same inits) — the fused row-MLP tail
    consumes them raw while checkpoints stay path-independent."""

    dim: int

    @nn.compact
    def __call__(self):
        from hydragnn_tpu.models.layers import DenseParams as _DenseParams

        k1, b1 = _DenseParams(self.dim, self.dim, name="lin1")()
        k2, b2 = _DenseParams(self.dim, self.dim, name="lin2")()
        return (k1, b1, k2, b2)


class InteractionPPBlock(nn.Module):
    hidden: int
    int_emb_size: int
    basis_emb_size: int
    num_before_skip: int
    num_after_skip: int
    sorted_hint: bool = False  # idx_ji is nondecreasing (builder order)
    tri_window: int = 0  # >0: fused edge-space kernel window (collate-vouched)
    tri_kernel: bool = False  # fused factored-basis kernel (ops/dn_tri.py)
    tri_builder: bool = False  # builder-backed wide-dim path (ops/dn_tri.py)
    num_radial: int = 6  # static R for the kernel's lane expansion

    @nn.compact
    def __call__(self, x_edge, rbf, sbf, idx_kj, idx_ji, triplet_mask,
                 perm_kj=None, radial=None, cbf_exp=None):
        e = x_edge.shape[0]
        # 0/1 mask: exact in any dtype; keeps the [T, *] streams in the
        # compute dtype instead of promoting them back to f32
        triplet_mask = triplet_mask.astype(x_edge.dtype)
        x_ji = _silu(nn.Dense(self.hidden, name="lin_ji")(x_edge))
        x_kj = _silu(nn.Dense(self.hidden, name="lin_kj")(x_edge))

        rbf_emb = nn.Dense(self.basis_emb_size, use_bias=False, name="lin_rbf1")(rbf)
        rbf_emb = nn.Dense(self.hidden, use_bias=False, name="lin_rbf2")(rbf_emb)
        x_kj = x_kj * rbf_emb
        x_kj = _silu(nn.Dense(self.int_emb_size, use_bias=False, name="lin_down")(x_kj))

        if self.tri_kernel:
            # factored-basis fused pass (ops/dn_tri.py): the sbf-embedding
            # MLP, the x_kj gather and the ji-scatter all run in VMEM —
            # the only [T, *] HBM streams left are cbf_exp and the index
            # tables.  Matmul-free param declarations keep the tree
            # identical to the nn.Dense layers they replace (checkpoint
            # path-independence, as in models/schnet._DenseParams).
            from hydragnn_tpu.models.layers import DenseParams as _DenseParams
            from hydragnn_tpu.ops.dn_tri import dimenet_triplet_mp

            sr = radial.shape[1]
            k1, _ = _DenseParams(sr, self.basis_emb_size, use_bias=False,
                                 name="lin_sbf1")()
            k2, _ = _DenseParams(self.basis_emb_size, self.int_emb_size,
                                 use_bias=False, name="lin_sbf2")()
            x_kj = dimenet_triplet_mp(
                radial.astype(x_edge.dtype), x_kj,
                cbf_exp.astype(x_edge.dtype), k1, k2, idx_kj, idx_ji,
                triplet_mask.astype(jnp.int32), perm_kj,
                self.num_radial)

            from hydragnn_tpu.utils.env import env_flag

            if (not env_flag("HYDRAGNN_DN_ROW_MLP_OFF")
                    and self.hidden <= 128 and self.int_emb_size <= 128):
                # fused row-local tail (ops/row_mlp.py): lin_up + skip
                # structure in one Pallas pass — the ~10 narrow [E, H]
                # Dense boundary streams collapse to 3 inputs + 1 output.
                # Matmul-free param declarations mirror the nn.Dense /
                # ResidualLayer tree (checkpoint path-independence).
                from hydragnn_tpu.ops.row_mlp import dimenet_post_mlp

                wb = list(_DenseParams(self.int_emb_size, self.hidden,
                                       use_bias=False, name="lin_up")())
                for i in range(self.num_before_skip):
                    wb += list(_ResidualParams(
                        self.hidden, name=f"before_skip_{i}")())
                wb += list(_DenseParams(self.hidden, self.hidden,
                                        name="lin")())
                for i in range(self.num_after_skip):
                    wb += list(_ResidualParams(
                        self.hidden, name=f"after_skip_{i}")())
                return dimenet_post_mlp(
                    x_kj, x_ji, x_edge, self.num_before_skip,
                    self.num_after_skip, *wb)
        elif self.tri_builder:
            # builder-backed fused path where the factored-basis gate
            # rejects on dims (S*R or the embedding sizes exceed its 64-
            # lane packing but still fit one 128-lane tile): the chain
            # fuses lin_sbf1/lin_sbf2 with the gather-multiply-scatter,
            # so the [T, D] embedding never hits HBM.  Matmul-free param
            # declarations keep the tree identical to the nn.Dense
            # layers (checkpoint path-independence).
            from hydragnn_tpu.models.layers import DenseParams
            from hydragnn_tpu.ops.dn_tri import dimenet_tri_builder

            k1, _ = DenseParams(sbf.shape[-1], self.basis_emb_size,
                                use_bias=False, name="lin_sbf1")()
            k2, _ = DenseParams(self.basis_emb_size, self.int_emb_size,
                                use_bias=False, name="lin_sbf2")()
            x_kj = dimenet_tri_builder(
                x_kj, sbf, triplet_mask.astype(jnp.int32), k1, k2,
                idx_kj, idx_ji, perm_kj)
        elif self.tri_window:
            sbf_emb = nn.Dense(self.basis_emb_size, use_bias=False, name="lin_sbf1")(sbf)
            sbf_emb = nn.Dense(self.int_emb_size, use_bias=False, name="lin_sbf2")(sbf_emb)
            # the triplet contraction IS message passing in EDGE space:
            # out[e'] = sum_{t: ji(t)=e'} x_kj[kj(t)] * sbf_emb[t] — one
            # fused W-window pass (fwd, and dx + dsbf_emb from one
            # backward pass over the triplets in ji order) instead of
            # gather + [T, D] materialization + sorted scatter
            from hydragnn_tpu.ops.fused_mp import gather_mul_segment_sum

            x_kj = gather_mul_segment_sum(
                x_kj, sbf_emb * triplet_mask[:, None], idx_kj, idx_ji,
                self.tri_window)
        else:
            sbf_emb = nn.Dense(self.basis_emb_size, use_bias=False, name="lin_sbf1")(sbf)
            sbf_emb = nn.Dense(self.int_emb_size, use_bias=False, name="lin_sbf2")(sbf_emb)
            # NOTE: this gather deliberately does NOT use gather_perm — its
            # backward (scatter-add over idx_kj) fuses into the surrounding
            # elementwise cotangent under XLA, and routing it through the
            # dense sorted scatter (which needs an extra g[perm] gather
            # first) was measured 12 ms/step SLOWER on the v5e sweep
            # config.  The rbf->triplet gather in spherical_basis keeps the
            # perm: its backward only runs under pos-grad (force training),
            # where the dense path halves the cost.
            msg = x_kj[idx_kj] * sbf_emb * triplet_mask[:, None]
            # build_triplets emits idx_ji in nondecreasing order (outer
            # loop over edge ids) — the dense-schedule sorted scatter
            # applies; passing the mask also schedule-skips padded-triplet
            # blocks (add_dimenet_extras parks them zero-valued at the
            # tail)
            x_kj = segment.sorted_segment_sum(
                msg, idx_ji, e, triplet_mask, sorted_hint=self.sorted_hint)
        x_kj = _silu(nn.Dense(self.hidden, use_bias=False, name="lin_up")(x_kj))

        h = x_ji + x_kj
        for i in range(self.num_before_skip):
            h = ResidualLayer(self.hidden, name=f"before_skip_{i}")(h)
        h = _silu(nn.Dense(self.hidden, name="lin")(h)) + x_edge
        for i in range(self.num_after_skip):
            h = ResidualLayer(self.hidden, name=f"after_skip_{i}")(h)
        return h


class OutputPPBlock(nn.Module):
    hidden: int
    out_emb_size: int
    out_dim: int
    num_layers: int = 1

    sorted_hint: bool = False  # receivers are nondecreasing (collate)

    @nn.compact
    def __call__(self, x_edge, rbf, receivers, num_nodes, edge_mask):
        g = nn.Dense(self.hidden, use_bias=False, name="lin_rbf")(rbf)
        x = g * x_edge
        x = segment.sorted_segment_sum(
            x, receivers, num_nodes, edge_mask, sorted_hint=self.sorted_hint)
        x = nn.Dense(self.out_emb_size, use_bias=False, name="lin_up")(x)
        for i in range(self.num_layers):
            x = _silu(nn.Dense(self.out_emb_size, name=f"lin_{i}")(x))
        return nn.Dense(self.out_dim, use_bias=False, name="lin_out")(x)


class DimeNetConv(nn.Module):
    """One DIMEStack 'conv': lin -> embed -> interaction -> output
    (reference get_conv, DIMEStack.py:79-116)."""

    in_dim: int
    out_dim: int
    num_radial: int
    num_spherical: int
    basis_emb_size: int
    int_emb_size: int
    out_emb_size: int
    num_before_skip: int
    num_after_skip: int
    envelope_exponent: int
    cutoff: float

    @nn.compact
    def __call__(self, x, pos, g: GraphBatch, train):
        hidden = self.out_dim if self.in_dim == 1 else self.in_dim
        assert hidden > 1, "DimeNet requires more than one hidden dimension."
        n = x.shape[0]
        src, dst = g.senders, g.receivers
        ex = g.extras
        idx_i, idx_j, idx_k = ex["dn_idx_i"], ex["dn_idx_j"], ex["dn_idx_k"]
        idx_kj, idx_ji = ex["dn_idx_kj"], ex["dn_idx_ji"]
        tmask = ex["dn_triplet_mask"]
        perm_kj = ex.get("dn_perm_kj")

        dist = jnp.sqrt(
            jnp.sum((pos[dst] - pos[src]) ** 2, axis=-1) + 1e-14
        )
        dist = jnp.where(g.edge_mask > 0, dist, self.cutoff)  # keep padding finite

        pos_i = pos[idx_i]
        v_ji = pos[idx_j] - pos_i
        v_ki = pos[idx_k] - pos_i
        a = jnp.sum(v_ji * v_ki, axis=-1)
        b = jnp.linalg.norm(jnp.cross(v_ji, v_ki) + 1e-14, axis=-1)
        angle = jnp.arctan2(b, a)

        rbf = BesselBasis(
            self.num_radial, self.cutoff, self.envelope_exponent, name="rbf"
        )(dist)
        # factored-basis fused triplet kernel gate: collate vouches the
        # window invariant ("dn_tri_ok"), the dims must fit the padded
        # lanes, and the sort invariants must hold (sorted_hint/perm)
        sr = self.num_spherical * self.num_radial
        tri_w = ex.get("dn_tri_window")
        tri_kernel = (
            ex.get("dn_tri_ok") is not None and perm_kj is not None
            and self.num_spherical <= 8 and sr <= 64
            and self.int_emb_size <= 64 and self.basis_emb_size <= 64
            # an explicit HYDRAGNN_DIMENET_FUSED_TRI opt-in wins: the
            # legacy T->E path stays reachable (and testable)
            and tri_w is None)
        # wide dims beyond the factored kernel's packing fall to the
        # builder-backed fused path (ops/dn_tri.dimenet_tri_builder) —
        # same window invariant, full-sbf geometry stream
        from hydragnn_tpu.ops.dn_tri import TRI_EMB_LIMIT, TRI_SBF_LIMIT

        tri_builder = (
            not tri_kernel and tri_w is None
            and ex.get("dn_tri_ok") is not None and perm_kj is not None
            and sr <= TRI_SBF_LIMIT
            and self.basis_emb_size <= TRI_EMB_LIMIT
            and self.int_emb_size <= TRI_EMB_LIMIT)
        if (ex.get("dn_tri_ok") is not None and perm_kj is not None
                and not (tri_kernel or tri_builder)):
            from hydragnn_tpu.ops.fused_block import note_fallback

            note_fallback("DimeNet", reason="width_gate",
                          sr=int(sr), int_emb=int(self.int_emb_size),
                          basis_emb=int(self.basis_emb_size))
        radial2 = cbf_exp = None
        if tri_kernel:
            radial2, cbf_exp = spherical_basis_factors(
                dist / self.cutoff, angle, self.num_spherical,
                self.num_radial, self.envelope_exponent)
            sbf = None
        else:
            sbf = spherical_basis(
                dist / self.cutoff,
                angle,
                idx_kj,
                self.num_spherical,
                self.num_radial,
                self.envelope_exponent,
                perm_kj=perm_kj,
            )
        # Mixed precision: the Bessel/Legendre recurrences are evaluated in
        # f32 (pos/dist/angle stay f32 for force grads and recurrence
        # stability), but the [T, S*R] / [E, R] basis STREAMS are cast to
        # the compute dtype here so the whole triplet-space chain — the
        # step's dominant HBM traffic (round-4 attribution: 9.4 GB/step of
        # [T, *] f32 streams at gather/scatter bandwidth) — runs in bf16
        # when the model does.  x carries the trainer's compute dtype;
        # under f32 training these casts are no-ops.
        rbf = rbf.astype(x.dtype)
        if sbf is not None:
            sbf = sbf.astype(x.dtype)

        h = nn.Dense(hidden, name="lin_in")(x)
        # embedding block (no atomic embedding; reference HydraEmbeddingBlock)
        rbf_e = _silu(nn.Dense(hidden, name="emb_lin_rbf")(rbf))
        x_edge = _silu(
            nn.Dense(hidden, name="emb_lin")(
                jnp.concatenate([h[dst], h[src], rbf_e], axis=-1)
            )
        )
        sorted_hint = bool(g.extras and "edge_perm_sender" in g.extras)
        # window encoded in the marker array's SHAPE (static under jit)
        tri_window = int(tri_w.shape[0]) if tri_w is not None else 0
        x_edge = InteractionPPBlock(
            hidden,
            self.int_emb_size,
            self.basis_emb_size,
            self.num_before_skip,
            self.num_after_skip,
            sorted_hint=sorted_hint,
            tri_window=tri_window,
            tri_kernel=tri_kernel,
            tri_builder=tri_builder,
            num_radial=self.num_radial,
            name="interaction",
        )(x_edge, rbf, sbf, idx_kj, idx_ji, tmask, perm_kj=perm_kj,
          radial=radial2, cbf_exp=cbf_exp)
        out = OutputPPBlock(
            hidden, self.out_emb_size, self.out_dim, num_layers=1,
            sorted_hint=sorted_hint, name="output"
        )(x_edge, rbf, dst, n, g.edge_mask)
        return out, pos


class DIMEStack(Base):
    has_batchnorm: bool = False

    def make_conv(self, name, in_dim, out_dim, last_layer):
        c = self.cfg
        return DimeNetConv(
            in_dim=in_dim,
            out_dim=out_dim,
            num_radial=c.num_radial,
            num_spherical=c.num_spherical,
            basis_emb_size=c.basis_emb_size,
            int_emb_size=c.int_emb_size,
            out_emb_size=c.out_emb_size,
            num_before_skip=c.num_before_skip,
            num_after_skip=c.num_after_skip,
            envelope_exponent=c.envelope_exponent,
            cutoff=c.radius,
            name=name,
        )
