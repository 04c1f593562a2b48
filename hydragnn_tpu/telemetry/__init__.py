"""Unified telemetry subsystem: per-step structured metrics, MFU/padding
accounting, pluggable sinks.

Entry points:
  - :class:`MetricsLogger` (logger.py) — the host-side spine the trainer
    threads per-step/per-epoch records through
  - :mod:`~hydragnn_tpu.telemetry.flops` — the flops-basis helpers shared
    with bench.py (one MFU definition, no drift)
  - :mod:`~hydragnn_tpu.telemetry.pipeline` — input-pipeline counters
    (queue depth, H2D transfer bytes, collate volume)
  - sinks (sinks.py): JSONL event log, CSV, stdout heartbeat, TensorBoard
  - :mod:`~hydragnn_tpu.telemetry.programs` — one ``program`` record per
    program JAX builds; its listeners are installed by this import, so
    that what is built before any logger exists is kept for the first

See docs/TELEMETRY.md for the record schema and knobs, and
tools/teleview.py for the JSONL summarizer.
"""

from hydragnn_tpu.telemetry.flops import (  # noqa: F401
    DEVICE_PEAKS,
    mfu_pct,
    peak_flops,
    step_cost_flops,
)
from hydragnn_tpu.telemetry.logger import (  # noqa: F401
    MetricsLogger,
    RingBuffer,
    TelemetryConfig,
    batch_pad_meta,
    waste_pct,
)
from hydragnn_tpu.telemetry.sinks import (  # noqa: F401
    CsvSink,
    JsonlSink,
    Sink,
    StdoutSink,
    TensorBoardSink,
    build_sinks,
)
from hydragnn_tpu.telemetry.trace import (  # noqa: F401
    SpanContext,
    SpanRecorder,
    chrome_trace,
    extract_trace_context,
)
