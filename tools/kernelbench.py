#!/usr/bin/env python
"""kernelbench: isolated aggregator microbenchmark — one command reproduces
the docs/PERF.md segment-reduce numbers.

Compares, at the documented sweep shapes, the backends the dispatchers in
graph/segment.py choose between:

  scatter   jax.ops.segment_sum (XLA sort/scatter path)
  dense     sorted dense-schedule scatter (ops/fused_mp.segment_sum_dense)
  poly      fused multi-moment pass (ops/poly_mp.segment_poly_dense)

Four moment sets:

  sum       plain segment sum — every backend
  pna       the PNA aggregator set (sum + sum-of-squares + max/min +
            degree): composed (2 scatter-sums + double-width segment_max +
            degree scatter) vs the ONE fused poly pass — the number behind
            the PNA end-to-end claim.
  matmul    the quantized-inference dense op (hydragnn_tpu/quant,
            docs/SERVING.md "Quantized inference"): an [E, F] x [F, 4F]
            activation matmul as f32, bf16, and int8-weight-dequantized-
            into-bf16 — isolating the per-op policy cost/win from
            end-to-end serving noise.  Runs on every backend (no Pallas);
            NOTE on CPU XLA emulates bf16, so the low-precision rows
            lose there — the HBM/MXU win is TPU-only.
  egcl      the EGNN interaction block (ops/egcl_mp.py, docs/PERF.md
            PR-15): composed XLA chain (2 gathers -> 2-layer edge MLP ->
            tanh coordinate gate -> TWO segment scatters) vs the ONE
            fused Pallas pass, each as f32 and bf16 — the number behind
            the EGNN mainline-MFU claim.  The fused rows are Pallas
            (skipped off-TPU without --force-pallas); bf16 carries the
            same CPU-emulation caveat as matmul.
  scf       SchNet's continuous-filter convolution (ops/scf_mp.py: the
            filter network made inside fused_mp's gather-multiply
            kernels): composed chain (filter MLP
            on the rbf expansion -> cutoff multiply -> gather-multiply ->
            segment sum) vs the one fused pass, f32 and bf16.
  gatfused  GATv2 edge attention (ops/gat_mp.py): composed chain (two
            gathers -> leaky-relu logits -> segment max -> exp ->
            THREE segment scatters) vs the one fused attention pass.
  cgcnn     CGCNN's gated sum (ops/cgcnn_mp.py, a spec on the builder):
            composed chain ([x_i, x_j, e_ij] concat -> gate MLP pair ->
            sigmoid*softplus -> segment sum) vs the one fused pass,
            f32 and bf16.

Methodology matches bench.py: each measurement jits a fori_loop of
``--inner`` serially-dependent applications (the loop carry feeds a hair of
each output back into the input, so nothing is hoisted or DCE'd and the
per-dispatch host overhead amortizes away), takes best-of-``--repeats``,
and forces completion with a host fetch of the result (bench.py's _sync).

On CPU the Pallas backends run in INTERPRET mode (minutes per call), so
they are skipped unless --force-pallas; the XLA backends still run, which
makes the tool usable as a smoke test anywhere.

Usage:
  python tools/kernelbench.py                     # all shapes, fwd+bwd
  python tools/kernelbench.py --shapes small --moments pna --no-grad
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

# runnable from anywhere: the repo root owns the hydragnn_tpu package
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

_BIG = 1e9

# the documented sweep shapes (docs/PERF.md: the isolated segment_sum
# measurement set and the flagship collate shape with degree <= 20)
SHAPES = {
    "tiny": dict(num_edges=2048, num_nodes=512, feat=32, max_deg=8),
    "small": dict(num_edges=32768, num_nodes=2560, feat=64, max_deg=16),
    "flagship": dict(num_edges=81920, num_nodes=10240, feat=64, max_deg=20),
}


def _make_problem(num_edges, num_nodes, feat, max_deg, seed=0):
    """Sorted-receiver edge structure with ~7% masked tail (the padding
    edges a bucketed loader ships), degree capped at max_deg.  The degree
    draw's lower bound is sized so the expected total OVERFILLS the edge
    array, then truncates — every shape gets the same ~93% fill instead
    of whatever randint(1, max_deg) happens to produce."""
    rng = np.random.RandomState(seed)
    e_real = int(num_edges * 0.93)
    avg_needed = num_edges / num_nodes
    lo = max(1, min(max_deg, int(np.ceil(2 * 0.95 * avg_needed)) - max_deg))
    deg = rng.randint(lo, max_deg + 1, num_nodes)
    ids = np.repeat(np.arange(num_nodes, dtype=np.int32), deg)
    e_real = min(e_real, ids.shape[0])
    receivers = np.full(num_edges, num_nodes - 1, np.int32)  # padding on
    receivers[:e_real] = ids[:e_real]                        # N-1, like
    mask = np.zeros(num_edges, np.float32)                   # collate
    mask[:e_real] = 1.0
    data = rng.randn(num_edges, feat).astype(np.float32)
    assert e_real >= int(num_edges * 0.9), (
        f"degree draw under-filled the shape: {e_real}/{num_edges}")
    return receivers, mask, data


def _sync(x):
    np.asarray(x).reshape(-1)[:1]


def _time_chain(fn, data, inner, repeats):
    """Best-of-N seconds per application of ``fn`` inside one compiled
    serially-dependent fori_loop (see module docstring)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def leaf_of(outs):
        if isinstance(outs, (tuple, list)):
            outs = outs[0]
        return outs.reshape(-1)[0]

    @jax.jit
    def run(d, s0):
        def body(_, carry):
            d, s = carry
            out = fn(d)
            s = s + leaf_of(out) * 1e-20
            return d + s * 1e-30, s
        return lax.fori_loop(0, inner, body, (d, s0))

    d0 = data
    out = run(d0, jnp.float32(0.0))
    _sync(out[1])
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = run(d0, jnp.float32(0.0))
        _sync(out[1])
        best = min(best, time.perf_counter() - t0)
    return best / inner


def _edge_structure(receivers, mask, num_nodes, rng):
    """Sender ids + sender-sort perm + int mask for the fused edge ops.

    Senders are drawn inside the receiver's 128-node block, the collate
    invariant (graphs never straddle a node block) the dense schedule's
    3-block gather windows rely on, and padding edges park on node N-1
    tail-sorted in BOTH orderings."""
    import jax.numpy as jnp

    e = receivers.shape[0]
    s_np = ((receivers // 128) * 128
            + rng.randint(0, 128, e)).astype(np.int32)
    s_np = np.minimum(s_np, num_nodes - 1)
    s_np[mask == 0] = num_nodes - 1  # padding edges: max sender id +
    perm = jnp.asarray(np.argsort(s_np, kind="stable")  # stable sort
                       .astype(np.int32))               # => tail
    em = jnp.asarray((mask > 0).astype(np.int32))
    return jnp.asarray(s_np), perm, em


def _backends(moments, receivers, mask, num_nodes, on_tpu, force_pallas,
              feat=0):
    """{name: data -> output} for the requested moment set."""
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.ops.fused_mp import segment_sum_dense
    from hydragnn_tpu.ops.poly_mp import segment_poly_dense

    r = jnp.asarray(receivers)
    m = jnp.asarray(mask)
    n = num_nodes
    run_pallas = on_tpu or force_pallas

    if moments == "matmul":
        # weight-only quantization A/B at this shape's feature width:
        # data is the [E, F] activation block, weights are [F, 4F]
        # (the MLP expansion every interaction block pays).  Weights
        # are built EAGERLY (concrete arrays) — closure state created
        # inside the timed trace would leak tracers.
        from hydragnn_tpu.quant import dequantize, quantize_int8

        rng = np.random.RandomState(11)
        w32 = jnp.asarray(rng.randn(feat, 4 * feat).astype(np.float32))
        w16 = w32.astype(jnp.bfloat16)
        wq = quantize_int8(w32)
        return {
            "mm-f32": lambda d: d @ w32,
            "mm-bf16": lambda d: (d.astype(jnp.bfloat16)
                                  @ w16).astype(jnp.float32),
            "mm-int8deq": lambda d: (d.astype(jnp.bfloat16)
                                     @ dequantize(wq)
                                     ).astype(jnp.float32),
        }

    if moments == "egcl":
        # EGNN interaction block: composed vs the one fused pass, f32 and
        # bf16.  Weights and edge structure are built EAGERLY like matmul.
        # The timed input is the NODE feature table (first n rows of the
        # [E, F] problem data — E > N at every sweep shape); edge
        # structure comes from _edge_structure (the collate invariants
        # the dense schedule relies on).
        from hydragnn_tpu.ops.egcl_mp import egcl_block

        rng = np.random.RandomState(13)
        e = receivers.shape[0]
        s, perm, em = _edge_structure(receivers, mask, n, rng)
        geo = jnp.asarray(np.concatenate(
            [rng.randn(e, 3).astype(np.float32) * 0.4,
             rng.rand(e, 1).astype(np.float32)], axis=1))
        w0 = jnp.asarray(rng.randn(2 * feat + 1, feat)
                         .astype(np.float32) * 0.1)
        b0 = jnp.asarray(rng.randn(feat).astype(np.float32) * 0.1)
        w1 = jnp.asarray(rng.randn(feat, feat).astype(np.float32) * 0.1)
        b1 = jnp.asarray(rng.randn(feat).astype(np.float32) * 0.1)
        wc0 = jnp.asarray(rng.randn(feat, feat).astype(np.float32) * 0.1)
        bc0 = jnp.asarray(rng.randn(feat).astype(np.float32) * 0.1)
        wc1 = jnp.asarray(rng.randn(feat, 1).astype(np.float32) * 0.3)
        diff, radial = geo[:, :3], geo[:, 3:]

        def composed(d, dt):
            x = d[:n].astype(dt)
            msg = jnp.concatenate(
                [x[s], x[r], radial.astype(dt)], axis=-1)
            msg = jax.nn.relu(msg @ w0.astype(dt) + b0.astype(dt))
            msg = jax.nn.relu(msg @ w1.astype(dt) + b1.astype(dt))
            msg = msg * m[:, None].astype(dt)
            agg = jax.ops.segment_sum(msg, s, num_segments=n)
            c = jax.nn.relu(msg @ wc0.astype(dt) + bc0.astype(dt))
            c = jnp.tanh(c @ wc1.astype(dt))
            trans = jnp.clip(diff.astype(dt) * c, -100.0, 100.0)
            psum = jax.ops.segment_sum(trans * m[:, None].astype(dt),
                                       s, num_segments=n)
            return agg.astype(jnp.float32), psum.astype(jnp.float32)

        def fused(d, dt):
            agg, psum = egcl_block(
                True, d[:n].astype(dt), geo, em, w0, b0, w1, b1,
                wc0, bc0, wc1, s, r, perm)
            return agg.astype(jnp.float32), psum

        out = {
            "composed-f32": lambda d: composed(d, jnp.float32),
            "composed-bf16": lambda d: composed(d, jnp.bfloat16),
        }
        if run_pallas:
            out["fused-f32"] = lambda d: fused(d, jnp.float32)
            out["fused-bf16"] = lambda d: fused(d, jnp.bfloat16)
        return out

    if moments == "scf":
        # SchNet continuous-filter conv: composed vs the filter network
        # made inside the gather-multiply kernels (ops/scf_mp.py).
        from hydragnn_tpu.models.layers import shifted_softplus
        from hydragnn_tpu.ops.scf_mp import scf_edge_pipeline

        rng = np.random.RandomState(17)
        e = receivers.shape[0]
        s, perm, em = _edge_structure(receivers, mask, n, rng)
        g = 32  # rbf expansion width (the flagship num_gaussians scale)
        rbf = jnp.asarray(rng.rand(e, g).astype(np.float32))
        # cutoff carries the edge mask (zero on padding — the contract)
        cm = jnp.asarray((rng.rand(e).astype(np.float32) * 0.9 + 0.1)
                         * mask)
        w0 = jnp.asarray(rng.randn(g, feat).astype(np.float32) * 0.1)
        b0 = jnp.asarray(rng.randn(feat).astype(np.float32) * 0.1)
        w1 = jnp.asarray(rng.randn(feat, feat).astype(np.float32) * 0.1)
        b1 = jnp.asarray(rng.randn(feat).astype(np.float32) * 0.1)

        def composed(d, dt):
            h = d[:n].astype(dt)
            filt = shifted_softplus(
                rbf.astype(dt) @ w0.astype(dt) + b0.astype(dt))
            filt = (filt @ w1.astype(dt) + b1.astype(dt)) \
                * cm[:, None].astype(dt)
            return jax.ops.segment_sum(
                h[s] * filt, r, num_segments=n).astype(jnp.float32)

        def fused(d, dt):
            return scf_edge_pipeline(
                d[:n].astype(dt), rbf, cm, em, w0, b0, w1, b1,
                s, r, perm).astype(jnp.float32)

        out = {
            "composed-f32": lambda d: composed(d, jnp.float32),
            "composed-bf16": lambda d: composed(d, jnp.bfloat16),
        }
        if run_pallas:
            out["fused-f32"] = lambda d: fused(d, jnp.float32)
            out["fused-bf16"] = lambda d: fused(d, jnp.bfloat16)
        return out

    if moments == "gatfused":
        # GATv2 edge attention: composed (2 gathers, segment max, exp,
        # 3 scatters) vs the one-pass fused attention kernel.
        from hydragnn_tpu.ops.gat_mp import gat_edge_attention_tiled

        rng = np.random.RandomState(19)
        e = receivers.shape[0]
        s, perm, em = _edge_structure(receivers, mask, n, rng)
        heads = 4
        fh = max(feat // heads, 1)
        hf = heads * fh
        att = rng.randn(heads, fh).astype(np.float32) * 0.2
        att_np = np.zeros((hf, heads), np.float32)
        for h_i in range(heads):
            att_np[h_i * fh:(h_i + 1) * fh, h_i] = att[h_i]
        att_mat = jnp.asarray(att_np)
        b_edge = jnp.asarray(np.repeat(mask[:, None], heads, axis=1))
        slope = 0.2

        def composed(d):
            x = d[:n, :hf]
            u = jax.nn.leaky_relu(x[s] + x[r], slope)
            logits = jnp.where(m[:, None] > 0, u @ att_mat, -_BIG)
            mx = jax.ops.segment_max(logits, r, num_segments=n)
            mx = jnp.where(mx <= -_BIG * 0.5, 0.0, mx)
            ex = jnp.exp(logits - jax.lax.stop_gradient(mx)[r]) * b_edge
            dsum = jax.ops.segment_sum(ex, r, num_segments=n)
            wmsg = (ex[:, :, None] * x[s].reshape(e, heads, fh)
                    ).reshape(e, hf)
            acc = jax.ops.segment_sum(wmsg, r, num_segments=n)
            return acc, mx, dsum

        def fused(d):
            x = d[:n, :hf]
            return gat_edge_attention_tiled(
                x, x, att_mat, s, r, perm, m, b_edge, (slope, fh))

        out = {"composed": composed}
        if run_pallas:
            out["fused"] = fused
        return out

    if moments == "cgcnn":
        # CGCNN gated sum: composed concat chain vs the builder spec.
        from hydragnn_tpu.ops.cgcnn_mp import cgcnn_gated_block

        rng = np.random.RandomState(23)
        e = receivers.shape[0]
        s, perm, em = _edge_structure(receivers, mask, n, rng)
        a = 16  # edge_attr width (bond-feature scale)
        ea = jnp.asarray(rng.rand(e, a).astype(np.float32))
        kf = jnp.asarray(rng.randn(2 * feat + a, feat)
                         .astype(np.float32) * 0.1)
        bf = jnp.asarray(rng.randn(feat).astype(np.float32) * 0.1)
        ks = jnp.asarray(rng.randn(2 * feat + a, feat)
                         .astype(np.float32) * 0.1)
        bs = jnp.asarray(rng.randn(feat).astype(np.float32) * 0.1)

        def composed(d, dt):
            x = d[:n].astype(dt)
            z = jnp.concatenate([x[r], x[s], ea.astype(dt)], axis=-1)
            gate = jax.nn.sigmoid(z @ kf.astype(dt) + bf.astype(dt))
            core = jax.nn.softplus(z @ ks.astype(dt) + bs.astype(dt))
            return jax.ops.segment_sum(
                gate * core * m[:, None].astype(dt), r,
                num_segments=n).astype(jnp.float32)

        def fused(d, dt):
            return cgcnn_gated_block(
                d[:n].astype(dt), ea, em, kf, bf, ks, bs,
                s, r, perm).astype(jnp.float32)

        out = {
            "composed-f32": lambda d: composed(d, jnp.float32),
            "composed-bf16": lambda d: composed(d, jnp.bfloat16),
        }
        if run_pallas:
            out["fused-f32"] = lambda d: fused(d, jnp.float32)
            out["fused-bf16"] = lambda d: fused(d, jnp.bfloat16)
        return out

    if moments == "sum":
        out = {
            "scatter": lambda d: jax.ops.segment_sum(
                d * m[:, None], r, num_segments=n),
        }
        if run_pallas:
            out["dense"] = lambda d: segment_sum_dense(
                d * m[:, None], r, n, valid=m)
            out["poly"] = lambda d: segment_poly_dense(
                d, r, n, ("sum",), valid=m)
        return out

    # pna: [sum, sq, max/min, degree] — composed vs one fused pass
    def composed(d):
        s = jax.ops.segment_sum(d * m[:, None], r, num_segments=n)
        q = jax.ops.segment_sum((d * d) * m[:, None], r, num_segments=n)
        cat = jnp.where(m[:, None] > 0,
                        jnp.concatenate([d, -d], axis=1), -_BIG)
        mxmn = jax.ops.segment_max(cat, r, num_segments=n)
        mxmn = jnp.where(mxmn <= -_BIG * 0.5, 0.0, mxmn)
        cnt = jax.ops.segment_sum(m, r, num_segments=n)
        return s, q, mxmn, cnt

    def dense_composed(d):
        # what PNA's composed path ACTUALLY ran under the r05 fused
        # backend (graph/segment.py scatter_segment routed the two sums
        # through the dense-schedule kernel; only max/min and degree
        # stayed XLA) — the honest pre-poly twin for the speedup claim
        dm = d * m[:, None]
        s = segment_sum_dense(dm, r, n, valid=m)
        q = segment_sum_dense(dm * d, r, n, valid=m)
        cat = jnp.where(m[:, None] > 0,
                        jnp.concatenate([d, -d], axis=1), -_BIG)
        mxmn = jax.ops.segment_max(cat, r, num_segments=n)
        mxmn = jnp.where(mxmn <= -_BIG * 0.5, 0.0, mxmn)
        cnt = jax.ops.segment_sum(m, r, num_segments=n)
        return s, q, mxmn, cnt

    out = {"scatter": composed}
    if run_pallas:
        out["dense-composed"] = dense_composed
        out["poly"] = lambda d: segment_poly_dense(
            d, r, n, ("sum", "sq", "mxmn", "cnt"), valid=m)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--shapes", default="small,flagship",
                    help=f"comma list from {sorted(SHAPES)}")
    ap.add_argument("--moments",
                    default="sum,pna,matmul,egcl,scf,gatfused,cgcnn",
                    help="comma list from "
                         "sum,pna,matmul,egcl,scf,gatfused,cgcnn")
    ap.add_argument("--inner", type=int, default=20,
                    help="op applications per compiled loop (default 20)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="best-of repeats (default 3)")
    ap.add_argument("--no-grad", action="store_true",
                    help="skip the fwd+bwd rows")
    ap.add_argument("--force-pallas", action="store_true",
                    help="run Pallas backends even off-TPU (interpret "
                         "mode: MINUTES per measurement)")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    on_tpu = jax.default_backend() == "tpu"
    print(f"kernelbench: backend={jax.default_backend()} "
          f"inner={args.inner} repeats={args.repeats}")
    if not on_tpu and not args.force_pallas:
        print("kernelbench: off-TPU — Pallas backends skipped "
              "(--force-pallas to run them in interpret mode)")

    results = {}
    for shape_name in [s for s in args.shapes.split(",") if s]:
        spec = SHAPES[shape_name]
        receivers, mask, data = _make_problem(**spec)
        data = jnp.asarray(data)
        for moments in [m for m in args.moments.split(",") if m]:
            fns = _backends(moments, receivers, mask, spec["num_nodes"],
                            on_tpu, args.force_pallas, feat=spec["feat"])
            for name, fn in fns.items():
                key = f"{shape_name}/{moments}/{name}"
                try:
                    fwd_s = _time_chain(fn, data, args.inner, args.repeats)
                    row = {"fwd_ms": round(fwd_s * 1e3, 4)}
                    if not args.no_grad:
                        def loss(d, fn=fn):
                            out = fn(d)
                            if not isinstance(out, (tuple, list)):
                                out = (out,)
                            return sum(jnp.sum(o.astype(jnp.float32) ** 2)
                                       for o in out)
                        g = jax.grad(loss)
                        bwd_s = _time_chain(g, data, args.inner,
                                            args.repeats)
                        row["fwdbwd_ms"] = round(bwd_s * 1e3, 4)
                    results[key] = row
                    print(f"  {key:34s} " + "  ".join(
                        f"{k}={v}" for k, v in row.items()))
                except Exception as e:  # noqa: BLE001 — keep sweeping
                    results[key] = {"error": repr(e)[:120]}
                    print(f"  {key:34s} FAILED {e!r}")
    print(json.dumps({"kernelbench": results}, separators=(",", ":")))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
