#!/usr/bin/env python3
"""Does Mosaic accept the fused kernels?  Answered WITHOUT a chip.

libtpu ships the TPU compiler, and ``jax.experimental.topologies`` can
hand JAX a compile-only v5e topology on a machine that has no TPU.  This
tool AOT-compiles one full fused train step (forward, backward, AdamW) per
arch against it, so a layout Mosaic rejects or a kernel that overruns
scoped VMEM shows up here, in the sandbox, as the compiler's own message —
not as a spent chip call.  It proves COMPILATION only: whether the numbers
are right, and every time or rate, still needs the chip
(``python chip_smoke.py`` through the chip tool).

    python tools/mosaic_aot.py                       # the default list
    python tools/mosaic_aot.py SchNet:1024:bfloat16  # ARCH:HIDDEN:DTYPE
    python tools/mosaic_aot.py PNA:512:float32:highest   # + matmul precision
    python tools/mosaic_aot.py cfconv:128:float32:shard_map  # the CFConv
        # op's gradient (filter made in the kernels) under shard_map on
        # the 2x2 topology, as the four-chip DP step runs it
    python tools/mosaic_aot.py moe_rows:20000:3072:bfloat16  # the routed
        # experts' value-and-grad (N nodes of width D, products in DTYPE)
        # with the two row-walk kernels and the megablox products
    python tools/mosaic_aot.py attention:20136:20x20x256:4096  # attention's
        # value-and-grad over N nodes, HEADSxKVxD, full layers banded to
        # SPAN (a fifth field: the window of a sliding layer), the splash
        # kernels under block tables made from traced node ids

The default list is every arch at h128 f32 plus the widths SchNet's
in-kernel filter network chooses its edge blocks for (128 at ``highest``;
256 / 512 / 1024 in f32 and bf16; 1024 at ``highest``), the sharded op,
the routed experts at the language-model cell's shapes in both dtypes, and
attention at three language-model cells' shapes (the last: 32 query heads
over 8 key/value heads of 64, eight multi-query calls).

Exit code 0 only if every target compiled.
"""

from __future__ import annotations

import contextlib
import os
import sys
import time

# compile-only: the process runs on the CPU backend and never opens a chip
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ["HYDRAGNN_AGGR_BACKEND"] = "fused"
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

TOPOLOGY = "v5e:2x2"


def compile_target(arch: str, hidden: int, dtype: str, precision, sharding):
    """Lower + compile ``arch``'s fused train step on bench.py's synthetic
    batch; returns (tpu_custom_call count, compile seconds)."""
    import jax
    import numpy as np

    import bench
    from hydragnn_tpu.models.create import create_model
    from hydragnn_tpu.train.optimizer import select_optimizer
    from hydragnn_tpu.train.trainer import (
        create_train_state, make_train_step)

    _, batch, _, cfg, _, _ = bench._build(
        arch, hidden=hidden, dtype=dtype, batch_size=256, trace_only=True)
    model = create_model(cfg)
    opt_spec = select_optimizer(bench.BENCH_OPTIMIZER)
    state = jax.eval_shape(
        lambda b: create_train_state(model, b, opt_spec), batch)

    def on_tpu(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=sharding), tree)

    ctx = (jax.default_matmul_precision(precision) if precision
           else contextlib.nullcontext())
    with ctx:
        lowered = jax.jit(make_train_step(model, cfg, opt_spec)).lower(
            on_tpu(state), on_tpu(batch))
        calls = lowered.as_text().count("tpu_custom_call")
        t0 = time.perf_counter()
        lowered.compile()
    return calls, time.perf_counter() - t0


def compile_cfconv_sharded(filters: int, dtype: str, devices):
    """Lower + compile the gradient of ``scf_edge_pipeline`` (wrt the
    features and the filter weights, all-reduced) under ``shard_map`` over
    all of ``devices`` — one flagship-sized edge list per device."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from hydragnn_tpu.ops.scf_mp import scf_edge_pipeline

    mesh = Mesh(np.asarray(devices), ("dp",))
    d, n, e, g = len(devices), 10240, 196608, 50
    per_dev = NamedSharding(mesh, P("dp"))
    shared = NamedSharding(mesh, P())

    def arg(shape, dt, sharding):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    ids = arg((d, e), jnp.int32, per_dev)
    args = (arg((d, n, filters), jnp.dtype(dtype), per_dev),
            arg((d, e, g), jnp.float32, per_dev),
            arg((d, e), jnp.float32, per_dev), ids,
            arg((g, filters), jnp.float32, shared),
            arg((filters,), jnp.float32, shared),
            arg((filters, filters), jnp.float32, shared),
            arg((filters,), jnp.float32, shared), ids, ids)

    def per_device(h, rbf, cm, em, k0, b0, k1, b1, send, recv):
        def loss(h_, w_):
            out = scf_edge_pipeline(h_[0], rbf[0], cm[0], em[0], *w_,
                                    send[0], recv[0])
            return jnp.sum(out.astype(jnp.float32) ** 2)
        dh, dw = jax.grad(loss, argnums=(0, 1))(h, (k0, b0, k1, b1))
        return dh, jax.lax.psum(dw, "dp")

    fn = jax.jit(jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P("dp"),) * 4 + (P(),) * 4 + (P("dp"),) * 2,
        out_specs=(P("dp"), P()), check_vma=False))
    lowered = fn.lower(*args)
    calls = lowered.as_text().count("tpu_custom_call")
    t0 = time.perf_counter()
    lowered.compile()
    return calls, time.perf_counter() - t0


def compile_moe_rows(nodes: int, width: int, dtype: str, sharding):
    """Lower + compile the value-and-grad of ``ops/moe.py:routed_experts``
    at the language-model cell's routing (8 of 256 experts held, top-10,
    expert width 1024, the default capacity).  ``backend="gmm"`` is said
    here: ``default_backend()`` asks the process, which runs on the CPU."""
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.ops import moe
    from hydragnn_tpu.parallel.share import LayerShare

    held, total, f = 8, 256, 1024
    share = LayerShare(total, held, 0, 8, 1, 0, 100352, 12544, 0)

    def loss(u, router, w1, w3, w2):
        y, stats = moe.routed_experts(
            u, router, w1, w3, w2, share, top_k=10, scale=2.5,
            compute_dtype=jnp.dtype(dtype), backend="gmm")
        return jnp.sum(y * y), stats

    def arg(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)

    lowered = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1, 2, 3, 4), has_aux=True)).lower(
        arg(nodes, width), arg(width, total), arg(held, width, f),
        arg(held, width, f), arg(held, f, width))
    calls = lowered.as_text().count("tpu_custom_call")
    t0 = time.perf_counter()
    lowered.compile()
    return calls, time.perf_counter() - t0


def compile_attention(nodes: int, shape: str, span: int, window, sharding):
    """Lower + compile the value-and-grad of ``ops/attention.py:
    graph_attention`` on the splash backend in bfloat16, ids and mask
    traced: the three kernels take their block tables from the batch."""
    import jax
    import jax.numpy as jnp

    from hydragnn_tpu.ops import attention

    heads, kv, d = map(int, shape.split("x"))

    def loss(q, k, v, gid, mask):
        o = attention.graph_attention(q, k, v, gid, mask, window=window,
                                      max_span=span, backend="splash")
        return jnp.sum(o.astype(jnp.float32) ** 2)

    def arg(shape, dt=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    lowered = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
        arg((nodes, heads, d)), arg((nodes, kv, d)), arg((nodes, kv, d)),
        arg((nodes,), jnp.int32), arg((nodes,), jnp.float32))
    calls = lowered.as_text().count("tpu_custom_call")
    t0 = time.perf_counter()
    lowered.compile()
    return calls, time.perf_counter() - t0


def main(argv) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from hydragnn_tpu.models.create import ALL_ARCHS

    topo = topologies.get_topology_desc(topology_name=TOPOLOGY,
                                        platform="tpu")
    dev = topo.devices[0]
    print(f"compile-only topology {TOPOLOGY}: {dev.device_kind} "
          f"(jax {jax.__version__}; no device is opened)", flush=True)
    # the ops pick Pallas interpret mode from jax.default_backend(); the
    # process backend is the CPU, so say "tpu" while tracing for the chip
    jax.default_backend = lambda: "tpu"

    targets = argv or (
        [f"{a}:128:float32" for a in ALL_ARCHS]
        + ["SchNet:128:float32:highest"]
        + [f"SchNet:{w}:{dt}" for w in (256, 512, 1024)
           for dt in ("float32", "bfloat16")]
        # ``highest`` adds the f32 dots' multi-pass scratch: the widest
        # kernels under it are the ones nearest the VMEM limit
        + ["SchNet:1024:float32:highest", "SchNet:1024:bfloat16:highest",
           "cfconv:128:float32:shard_map",
           "moe_rows:20000:3072:bfloat16", "moe_rows:20000:3072:float32",
           "attention:20136:20x20x256:4096", "attention:20000:6x1x128:5580",
           "attention:20000:9x1x128:5580:512",
           "attention:24000:32x8x64:4096"])
    failed = 0
    for t in targets:
        arch, hidden, dtype, *rest = t.split(":")
        try:
            if rest == ["shard_map"]:
                calls, secs = compile_cfconv_sharded(
                    int(hidden), dtype, topo.devices)
            elif arch == "attention":
                calls, secs = compile_attention(
                    int(hidden), dtype, int(rest[0]),
                    int(rest[1]) if rest[1:] else None,
                    SingleDeviceSharding(dev))
            elif arch == "moe_rows":
                calls, secs = compile_moe_rows(
                    int(hidden), int(dtype), rest[0],
                    SingleDeviceSharding(dev))
            else:
                calls, secs = compile_target(
                    arch, int(hidden), dtype, rest[0] if rest else None,
                    SingleDeviceSharding(dev))
        except Exception as e:  # noqa: BLE001 — the compiler's message IS the result; reported and counted
            failed += 1
            print(f"FAIL {t}: {type(e).__name__}: {str(e)[:4000]}",
                  flush=True)
            continue
        print(f"OK   {t}: tpu_custom_calls={calls} compile={secs:.1f}s",
              flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
