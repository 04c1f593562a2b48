"""A gated delta rule (Gated DeltaNet) over each graph's nodes: linear
attention whose state is a MATRIX a head.

A graph's nodes are contiguous on the packed node axis and in order
(graph/batch.py collate).  Along the nodes ``t`` of ONE graph, per value
head ``h`` (which reads key head ``h // (H_v / H_k)``), with ``S`` [d_k,
d_v]::

    S' = exp(g_t) S_{t-1}            r_t = v_t - S'^T k_t
    S_t = S' + beta_t k_t r_t^T      o_t = S_t^T q_t

``g_t <= 0`` is ONE scalar a head and node (the log of the decay), ``beta_t``
in (0, 1) the writing strength.  ``S`` is zero before a graph's first node;
padding nodes neither feed nor read a state (their rows give zero).
Nothing crosses a graph boundary.

``chunked`` (the TPU path) is the published chunked form (Yang, Kautz,
Hatamizadeh, "Gated Delta Networks", arXiv:2412.06464) at a chunk of ``C``
nodes.  With ``gamma`` the running sum of ``g`` from the chunk's first
node, ``D[t, s] = exp(gamma_t - gamma_s)`` for ``s <= t`` in one graph
(else 0: no exponent is ever positive), and ``v'_t = beta_t r_t`` the
pseudo-values that are really written::

    A  = strict_lower(diag(beta) (K K^T * D))     T = (I + A)^-1 diag(beta)
    W  = T (K * exp(gamma))                       U = T V
    V' = U - W S            O = (Q * exp(gamma)) S + (Q K^T * D) V'
    S_out = exp(gamma_C) S + (K * exp(gamma_C - gamma))^T V'

``S`` the state entering the chunk.  Unlike a state-space scan's, a
chunk's own contribution ``V'`` depends on the entering state, so the
chunks are walked by a ``lax.scan`` whose step holds the three products
with ``S`` (``W S``, ``Q S``, the update); everything else is batched over
the chunks ahead of it and behind it.  **Graph boundaries are exact and use
no infinite decay**, as in ops/ssm.py: ``D`` carries ``same graph(s, t)``
(ids compared, both nodes real), so ``A`` is block diagonal by graph and
its inverse is too; a row reads the entering state (``W``'s and ``Q``'s
``exp(gamma)``) only where it is of the previous chunk's last node's
graph; the update takes the nodes of the chunk's last node's graph; and the
chunk-to-chunk carry is kept only where both last nodes are of one graph.

**The inverse** of the unit lower-triangular ``I + A`` is exact block
elimination by doubling: with ``X`` the inverse of the diagonal blocks of
size ``b`` and ``A_b`` the entries of ``A`` in the lower-left ``b x b``
corner of each ``2b`` block, ``X <- X - X A_b X`` is the inverse of the
``2b`` blocks (``[[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1,
Q^-1]]``): ``log2 C`` rounds of two batched [C, C] products, float32 at
HIGHEST, no division, nothing sequential in the rows, and none of a power
series' cancellation.  Its backward pass is written down (``dA = -X^T dX
X^T``: two products, not the rounds' transposes).

``sequential`` is the recurrence itself, one node a step of a
``lax.scan``: the CPU path and the twin the tests hold the chunked form to.

Precision: the products take operands in ``v``'s dtype (bfloat16 in the
benchmark's cell; ``T`` and the entering state are rounded to it where a
product reads them) and accumulate in float32; ``g``, ``beta``, the running
sums, every decay, the inverse and the carried state are float32.  The
backward pass is JAX's own of either form but for the inverse.  The layer
that calls this is recomputed in the backward pass (models/qwen3_next.py),
so the [chunks, heads, C, C] matrices and the per-chunk states live for one
layer's backward pass at a time, but for the inverse, which carries a name
(``GDN_INV``) that the layer's checkpoint keeps: [chunks, H_v, C, C]
float32 as computed, 106 MB a layer at 203 chunks of 64 and 32 heads.  Kept,
the recomputed forward stops at ``A`` and the rounds run once a step; their
backward rule reads nothing else.  ``T`` is one elementwise pass from the
inverse and is rebuilt.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from hydragnn_tpu.ops.ssm import _real, graph_starts
from hydragnn_tpu.utils.scope import phase

_HIGHEST = lax.Precision.HIGHEST

# What a checkpoint that wraps a call of the ``chunked`` backend may keep by
# name: the inverse ``(I + A)^-1``, float32 [chunks, H_v, C, C] as computed,
# named inside ``unit_lower_inverse``'s ``custom_vjp`` on the very array its
# backward rule reads.  With it kept the backward pass runs that rule's two
# products and not the rounds again.  ``inverse_bytes`` is its size.
GDN_INV = "gdn.scan.inverse"


def default_backend() -> str:
    return "chunked" if jax.default_backend() == "tpu" else "sequential"


def _sequential(q, k, v, g, beta, node_gid, real):
    per_key = v.shape[1] // k.shape[1]
    start = graph_starts(node_gid, real)
    # each value head reads its key head's q and k
    q32 = jnp.repeat(q.astype(jnp.float32), per_key, axis=1)
    k32 = jnp.repeat(k.astype(jnp.float32), per_key, axis=1)

    def step(s, row):
        qt, kt, vt, gt, bt, first, live = row
        s = jnp.where(first, 0.0, s)
        decayed = jnp.exp(gt)[:, None, None] * s
        r = vt - jnp.einsum("hkv,hk->hv", decayed, kt)
        new = decayed + (bt[:, None] * kt)[:, :, None] * r[:, None, :]
        s = jnp.where(live, new, s)
        return s, jnp.where(live, jnp.einsum("hkv,hk->hv", s, qt), 0.0)

    _, o = lax.scan(
        step, jnp.zeros((v.shape[1], k.shape[2], v.shape[2]), jnp.float32),
        (q32, k32, v.astype(jnp.float32), g, beta, start, real))
    return o


@jax.custom_vjp
def unit_lower_inverse(a):
    """``(I + a)^-1`` for ``a`` [..., C, C] strictly lower triangular, ``C``
    a power of two: float32, block elimination by doubling (the module's
    docstring)."""
    c = a.shape[-1]
    row = jnp.arange(c)[:, None]
    col = jnp.arange(c)[None, :]
    # blocks of one: the lower-left corner of each 2-block is one entry
    x = jnp.eye(c, dtype=jnp.float32) - jnp.where(
        (row % 2 == 1) & (col == row - 1), a, 0.0)
    b = 2
    while b < c:
        corner = (row // b % 2 == 1) & (col // b == row // b - 1)
        x = x - jnp.matmul(
            jnp.matmul(x, jnp.where(corner, a, 0.0), precision=_HIGHEST),
            x, precision=_HIGHEST)
        b *= 2
    return x


def _inverse_fwd(a):
    # the name on the array that is both result and residual: one put on a
    # caller's copy would mark another variable, and keep nothing
    x = checkpoint_name(unit_lower_inverse(a), GDN_INV)
    return x, x


def _inverse_bwd(x, dx):
    xt = jnp.swapaxes(x, -1, -2)
    return (-jnp.matmul(jnp.matmul(xt, dx, precision=_HIGHEST), xt,
                        precision=_HIGHEST),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


def inverse_bytes(n, heads, chunk, backend=None):
    """Bytes of the array named ``GDN_INV`` in ONE ``graph_gated_delta``
    call on ``n`` nodes and ``heads`` value heads: a number of the shapes.
    0 on the ``sequential`` backend, which computes no inverse and names
    nothing."""
    if (backend or default_backend()) != "chunked":
        return 0
    return -(-n // chunk) * heads * chunk * chunk * 4


def _chunked(q, k, v, g, beta, node_gid, real, chunk):
    n, key_heads, dk = k.shape
    heads, dv = v.shape[1], v.shape[2]
    per_key = heads // key_heads
    dtype = v.dtype
    pad = -n % chunk
    nc = (n + pad) // chunk

    def chunks(a, fill=0):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1),
                    constant_values=fill)
        return a.reshape((nc, chunk) + a.shape[1:])

    # padding nodes: no decay, nothing written
    g = jnp.where(real[:, None], g, 0.0)
    beta = jnp.where(real[:, None], beta, 0.0)
    qc, kc, vc = chunks(q), chunks(k), chunks(v)
    gid, live = chunks(node_gid, -1), chunks(real)
    bc = chunks(beta).transpose(0, 2, 1)                 # [nc, H, C]
    # cum[c, h, t]: sum of g over the chunk's nodes up to t, <= 0
    cum = jnp.cumsum(chunks(g), axis=1).transpose(0, 2, 1)
    same = (gid[:, :, None] == gid[:, None, :]) & (
        live[:, :, None] & live[:, None, :])
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    # D[c, h, t, s] = exp(cum_t - cum_s) where s <= t in one graph, else 0
    decay = jnp.exp(jnp.where(
        (same & causal)[:, None], cum[..., :, None] - cum[..., None, :],
        -jnp.inf))
    # K K^T and Q K^T once a key head, then every value head weighs them
    kk = jnp.repeat(jnp.einsum("ctgd,csgd->cgts", kc, kc,
                               preferred_element_type=jnp.float32),
                    per_key, axis=1)
    qk = jnp.repeat(jnp.einsum("ctgd,csgd->cgts", qc, kc,
                               preferred_element_type=jnp.float32),
                    per_key, axis=1)
    a = jnp.where(jnp.tril(causal, -1), bc[..., :, None] * kk * decay, 0.0)
    t = (unit_lower_inverse(a) * bc[..., None, :]).astype(dtype)

    # which rows read the entering state, which feed the leaving one
    last_gid, last_live = gid[:, -1], live[:, -1]
    prev_gid = jnp.concatenate([last_gid[:1] - 1, last_gid[:-1]])
    prev_live = jnp.concatenate([jnp.zeros((1,), bool), last_live[:-1]])
    reads = jnp.where(
        ((gid == prev_gid[:, None]) & live & prev_live[:, None])[:, None],
        jnp.exp(cum), 0.0)                               # [nc, H, C]
    to_end = jnp.exp(jnp.where(
        ((gid == last_gid[:, None]) & live & last_live[:, None])[:, None],
        cum[..., -1:] - cum, -jnp.inf))                  # [nc, H, C]
    # the carry from chunk to chunk: kept where both last nodes are of one
    # graph (graphs are contiguous: no graph starts in between)
    keep = (last_gid == prev_gid) & last_live & prev_live
    carry = jnp.where(keep[:, None], jnp.exp(cum[..., -1]), 0.0)  # [nc, H]

    kh = jnp.repeat(kc, per_key, axis=2).transpose(0, 2, 1, 3)
    qh = jnp.repeat(qc, per_key, axis=2).transpose(0, 2, 1, 3)
    vh = vc.transpose(0, 2, 1, 3)                        # [nc, H, C, dv]

    def scaled(x, by):
        return (x.astype(jnp.float32) * by[..., None]).astype(dtype)

    w = jnp.einsum("chts,chsd->chtd", t, scaled(kh, reads),
                   preferred_element_type=jnp.float32).astype(dtype)
    u = jnp.einsum("chts,chsd->chtd", t, vh,
                   preferred_element_type=jnp.float32)

    def step(s, row):
        w_c, u_c, q_c, k_c, keep_c = row
        sd = s.astype(dtype)
        new = (u_c - jnp.einsum("htk,hkv->htv", w_c, sd,
                                preferred_element_type=jnp.float32)
               ).astype(dtype)
        read = jnp.einsum("htk,hkv->htv", q_c, sd,
                          preferred_element_type=jnp.float32)
        s = keep_c[:, None, None] * s + jnp.einsum(
            "htk,htv->hkv", k_c, new, preferred_element_type=jnp.float32)
        return s, (new, read)

    _, (new, read) = lax.scan(
        step, jnp.zeros((heads, dk, dv), jnp.float32),
        (w, u, scaled(qh, reads), scaled(kh, to_end), carry))
    o = read + jnp.einsum("chts,chsv->chtv", (qk * decay).astype(dtype), new,
                          preferred_element_type=jnp.float32)
    return o.transpose(0, 2, 1, 3).reshape(nc * chunk, heads, dv)[:n]


def graph_gated_delta(q, k, v, g, beta, node_gid, node_mask=None, *,
                      chunk=64, backend=None):
    """The rule above for ``q`` / ``k`` [N, H_k, d_k] (as they enter the
    rule: normed, ``q`` scaled), ``v`` [N, H_v, d_v] (value head ``h`` reads
    key head ``h // (H_v / H_k)``), ``g`` [N, H_v] (<= 0) and ``beta`` [N,
    H_v]: float32 [N, H_v, d_v].  ``chunk`` a power of two."""
    backend = backend or default_backend()
    real = _real(node_mask, v.shape[0])
    g, beta = g.astype(jnp.float32), beta.astype(jnp.float32)
    with phase("gdn.scan"):
        if backend == "sequential":
            return _sequential(q, k, v, g, beta, node_gid, real)
        if backend == "chunked":
            if chunk & (chunk - 1):
                raise ValueError(f"chunk {chunk} is not a power of two")
            return _chunked(q, k, v, g, beta, node_gid, real, chunk)
        raise ValueError(f"unknown gated-delta backend {backend!r}")
