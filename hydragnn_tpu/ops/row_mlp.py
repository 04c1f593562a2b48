"""Fused row-local residual-MLP chain: DimeNet's post-interaction block
(lin_up -> +x_ji -> before-skip residual layers -> lin+skip -> after-skip
residual layers) in ONE Pallas pass per direction.

Motivation (round-5 DimeNet attribution, docs/PERF.md): after the
triplet kernel and tight padding, the step's top HBM consumers are the
interaction block's ~19 NARROW [E, 64] Dense ops — each one a
bandwidth-bound [E,64]@[64,64] matmul (32 flops/byte at f32 against the
v5e's ~240 flops/byte ridge) whose input/output stream through HBM at
every fusion boundary.  Rows are independent, weights are tiny
([64,64] x ~8 fits VMEM many times over), so the whole chain runs per
row-block in VMEM: 3 input streams + 1 output stream replace ~16
boundary streams forward (backward recomputes activations from the same
inputs and accumulates dW in constant-mapped blocks).

Chain (reference InteractionPPBlock tail, DIMEStack.py / PyG
DimeNet++):

    u  = silu(W_up @ tri)                       # no bias
    h  = x_ji + u
    for i in range(n_before):  h = h + silu(W2_i silu(W1_i h + b1_i) + b2_i)
    h  = silu(W h + b) + x_edge
    for i in range(n_after):   h = h + silu(W2_i silu(W1_i h + b1_i) + b2_i)

n_before / n_after are STATIC (config); the kernel body unrolls them.
Requires hidden <= 128 and int_emb <= 128 (one lane block each).
Weights ride one stacked [L, 128, 128] constant (L = 1 + 2*(n_before +
n_after) + 1) with biases folded into a [L, 8, 128] block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from hydragnn_tpu.ops.aggregate import _round_up
from hydragnn_tpu.ops.fused_block import _dot

_RB = 512   # rows per grid step
_HP = 128   # padded feature lanes


def _silu(z):
    return z * jax.nn.sigmoid(z)


def _dsilu(z):
    s = jax.nn.sigmoid(z)
    return s * (1.0 + z * (1.0 - s))


def _chain_fwd(tri, x_ji, x_edge, w_ref, b_ref, n_before, n_after, dt):
    """Run the chain, returning (h, pre-activation list, input list) —
    pres[k]/ins[k] are the k-th dense's pre-activation and input."""
    pres, ins = [], []

    def dense(k, v):
        ins.append(v)
        z = _dot(v, w_ref[k], ((1,), (0,)), dt) + b_ref[k][0:1, :]
        pres.append(z)
        return z

    k = 0
    h = x_ji + _silu(dense(k, tri)); k += 1
    for _ in range(n_before):
        t = _silu(dense(k, h)); k += 1
        h = h + _silu(dense(k, t)); k += 1
    h = _silu(dense(k, h)) + x_edge; k += 1
    for _ in range(n_after):
        t = _silu(dense(k, h)); k += 1
        h = h + _silu(dense(k, t)); k += 1
    return h, pres, ins


def _fwd_kernel(n_before, n_after, tri_ref, xji_ref, xe_ref, w_ref, b_ref,
                out_ref):
    dt = w_ref.dtype
    h, _p, _i = _chain_fwd(
        tri_ref[:].astype(jnp.float32), xji_ref[:].astype(jnp.float32),
        xe_ref[:].astype(jnp.float32), w_ref, b_ref, n_before, n_after, dt)
    out_ref[:] = h


def _bwd_kernel(n_before, n_after, tri_ref, xji_ref, xe_ref, w_ref, b_ref,
                g_ref, dtri_ref, dxji_ref, dxe_ref, dw_ref, db_ref):
    from jax.experimental import pallas as pl

    s = pl.program_id(0)
    dt = w_ref.dtype

    @pl.when(s == 0)
    def _init():
        dw_ref[:] = jnp.zeros_like(dw_ref)
        db_ref[:] = jnp.zeros_like(db_ref)

    _h, pres, ins = _chain_fwd(
        tri_ref[:].astype(jnp.float32), xji_ref[:].astype(jnp.float32),
        xe_ref[:].astype(jnp.float32), w_ref, b_ref, n_before, n_after, dt)
    g = g_ref[:].astype(jnp.float32)

    def back(k, dz_post):
        """Backward through dense k given d(silu(z_k)); returns d(input)."""
        dz = dz_post * _dsilu(pres[k])
        dw_ref[k] += _dot(ins[k], dz, ((0,), (0,)), dt)
        db_ref[k] += jnp.broadcast_to(
            jnp.sum(dz, axis=0, keepdims=True) / db_ref.shape[1],
            (db_ref.shape[1], db_ref.shape[2]))
        return _dot(dz, w_ref[k], ((1,), (1,)), dt)

    k = 1 + 2 * (n_before + n_after)  # last dense index
    dh = g
    for _ in range(n_after):
        # h = h_prev + silu(D2(silu(D1(h_prev))))
        dt2 = back(k, dh); k -= 1
        dh = dh + back(k, dt2); k -= 1
    # h = silu(D(h_prev)) + x_edge
    dxe_ref[:] = dh
    dh = back(k, dh); k -= 1
    for _ in range(n_before):
        dt2 = back(k, dh); k -= 1
        dh = dh + back(k, dt2); k -= 1
    # h0 = x_ji + silu(D_up(tri))
    dxji_ref[:] = dh
    dtri_ref[:] = back(k, dh)


def _pack_rows(a, e_pad, dt):
    e, d = a.shape
    out = jnp.zeros((e_pad, _HP), dt)
    return out.at[:e, :d].set(a.astype(dt))


def _pack_wb(ws, bs, dt):
    L = len(ws)
    w_p = jnp.zeros((L, _HP, _HP), jnp.float32)
    b_p = jnp.zeros((L, 8, _HP), jnp.float32)
    for k, (w, b) in enumerate(zip(ws, bs)):
        di, do = w.shape
        w_p = w_p.at[k, :di, :do].set(w.astype(jnp.float32))
        if b is not None:
            b_p = b_p.at[k, :, :do].set(
                jnp.broadcast_to(b.astype(jnp.float32), (8, do)))
    return w_p.astype(dt), b_p.astype(dt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def dimenet_post_mlp(tri, x_ji, x_edge, n_before, n_after, *wb):
    """The InteractionPPBlock tail as one fused row-local pass.

    ``wb`` is the flat (w_0, b_0, w_1, b_1, ...) parameter list in chain
    order: lin_up (bias None), then n_before x (lin1, lin2) residual
    pairs, then lin, then n_after x (lin1, lin2) pairs.  Differentiable
    wrt tri/x_ji/x_edge and every w/b.  hidden and int_emb must be
    <= 128."""
    return _post_fwd(tri, x_ji, x_edge, n_before, n_after, wb)


def _n_dense(n_before, n_after):
    return 2 + 2 * (n_before + n_after)


def _post_fwd(tri, x_ji, x_edge, n_before, n_after, wb):
    from jax.experimental import pallas as pl

    interpret = jax.default_backend() != "tpu"
    e, h = x_edge.shape
    bf16 = x_edge.dtype == jnp.bfloat16
    dt = jnp.bfloat16 if bf16 else jnp.float32
    e_pad = _round_up(max(e, 1), _RB)
    ws, bs = list(wb[0::2]), list(wb[1::2])
    w_p, b_p = _pack_wb(ws, bs, dt)
    tri_p = _pack_rows(tri, e_pad, dt)
    xji_p = _pack_rows(x_ji, e_pad, dt)
    xe_p = _pack_rows(x_edge, e_pad, dt)
    grid = e_pad // _RB

    out = pl.pallas_call(
        functools.partial(_fwd_kernel, n_before, n_after),
        out_shape=jax.ShapeDtypeStruct((e_pad, _HP), jnp.float32),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((_RB, _HP), lambda s: (s, 0)),
            pl.BlockSpec((_RB, _HP), lambda s: (s, 0)),
            pl.BlockSpec((_RB, _HP), lambda s: (s, 0)),
            pl.BlockSpec(w_p.shape, lambda s: (0, 0, 0)),
            pl.BlockSpec(b_p.shape, lambda s: (0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((_RB, _HP), lambda s: (s, 0)),
        interpret=interpret,
        name="dn_post_mlp_fwd",
    )(tri_p, xji_p, xe_p, w_p, b_p)
    return out[:e, :h].astype(x_edge.dtype)


def _post_vjp_fwd(tri, x_ji, x_edge, n_before, n_after, *wb):
    out = _post_fwd(tri, x_ji, x_edge, n_before, n_after, wb)
    return out, (tri, x_ji, x_edge, wb)


def _post_vjp_bwd(n_before, n_after, res, g):
    from jax.experimental import pallas as pl

    tri, x_ji, x_edge, wb = res
    interpret = jax.default_backend() != "tpu"
    e, h = x_edge.shape
    d = tri.shape[1]
    bf16 = x_edge.dtype == jnp.bfloat16
    dt = jnp.bfloat16 if bf16 else jnp.float32
    e_pad = _round_up(max(e, 1), _RB)
    ws, bs = list(wb[0::2]), list(wb[1::2])
    L = len(ws)
    w_p, b_p = _pack_wb(ws, bs, dt)
    tri_p = _pack_rows(tri, e_pad, dt)
    xji_p = _pack_rows(x_ji, e_pad, dt)
    xe_p = _pack_rows(x_edge, e_pad, dt)
    g_p = _pack_rows(g, e_pad, dt)
    grid = e_pad // _RB

    row = pl.BlockSpec((_RB, _HP), lambda s: (s, 0))
    const_w = pl.BlockSpec(w_p.shape, lambda s: (0, 0, 0))
    const_b = pl.BlockSpec(b_p.shape, lambda s: (0, 0, 0))
    dtri_p, dxji_p, dxe_p, dw_p, db_p = pl.pallas_call(
        functools.partial(_bwd_kernel, n_before, n_after),
        out_shape=[
            jax.ShapeDtypeStruct((e_pad, _HP), jnp.float32),
            jax.ShapeDtypeStruct((e_pad, _HP), jnp.float32),
            jax.ShapeDtypeStruct((e_pad, _HP), jnp.float32),
            jax.ShapeDtypeStruct((L, _HP, _HP), jnp.float32),
            jax.ShapeDtypeStruct((L, 8, _HP), jnp.float32),
        ],
        grid=(grid,),
        in_specs=[row, row, row, const_w, const_b, row],
        out_specs=[row, row, row,
                   pl.BlockSpec((L, _HP, _HP), lambda s: (0, 0, 0)),
                   pl.BlockSpec((L, 8, _HP), lambda s: (0, 0, 0))],
        interpret=interpret,
        name="dn_post_mlp_bwd",
    )(tri_p, xji_p, xe_p, w_p, b_p, g_p)

    grads = [dtri_p[:e, :d].astype(tri.dtype),
             dxji_p[:e, :h].astype(x_ji.dtype),
             dxe_p[:e, :h].astype(x_edge.dtype)]
    out_wb = []
    for k, (w, b) in enumerate(zip(ws, bs)):
        di, do = w.shape
        out_wb.append(dw_p[k, :di, :do].astype(w.dtype))
        out_wb.append(None if b is None
                      else jnp.sum(db_p[k, :, :do], axis=0).astype(b.dtype))
    return tuple(grads) + tuple(out_wb)


dimenet_post_mlp.defvjp(_post_vjp_fwd, _post_vjp_bwd)
