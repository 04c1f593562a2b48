"""TPU kernels and the aggregation-backend decision."""

from hydragnn_tpu.ops.aggregate import aggr_backend  # noqa: F401
