"""The aggregation-path decision: which backend, and who reads it.

The reference exposes HYDRAGNN_AGGR_BACKEND to switch PyG's aggregation
between torch-scatter and its native fallback (reference
hydragnn/train/train_validate_test.py:373-378).  Here the same knob selects
how the message-passing core lowers on the device:

- ``scatter`` (default): gather + ``jax.ops.segment_sum`` in plain XLA.
- ``fused``: collate attaches the fused-kernel marker and the
  gather->multiply->segment-sum core runs as one sorted-receiver
  dense-schedule Pallas pass (ops/fused_mp.py and its siblings, dispatched
  via graph/segment.py:gather_mul_segment and the models' own gates).
  Plain ``segment.segment_sum`` calls are ``jax.ops.segment_sum`` under
  either backend.

:func:`aggr_backend` is the ONLY reader of the variable (tests/test_lint.py
holds that); :func:`backend_scope` is the only scoped writer.
"""

from __future__ import annotations

import contextlib
import os

import jax.numpy as jnp

_ENV = "HYDRAGNN_AGGR_BACKEND"

# THE backend vocabulary (config validation in run_training.py imports it
# — one definition, no drift between the two validation points)
KNOWN_BACKENDS = ("scatter", "fused")
_warned_unknown = set()


def aggr_backend() -> str:
    """Current backend name.  The env knob is read at collate time and at
    TRACE time: a jitted caller (every real train/eval step) pins whichever
    backend was active when it was first traced, so set the knob before
    building the step — flipping it mid-process does not retrace cached
    executables.

    An unrecognized env value warns ONCE and behaves as ``scatter``
    (every backend check misses): a typo like ``fusd`` would otherwise
    silently lose the whole fused path AND evade the fallback telemetry,
    which only compares against the exact string ``fused``."""
    v = os.environ.get(_ENV, "scatter").strip().lower()
    if v not in KNOWN_BACKENDS and v not in _warned_unknown:
        _warned_unknown.add(v)
        import warnings

        warnings.warn(
            f"{_ENV}={v!r} is not one of {KNOWN_BACKENDS};"
            " every aggregation will take the scatter path", stacklevel=2)
    return v


@contextlib.contextmanager
def backend_scope(name, *, override: bool = True):
    """Set the backend for the duration of the block and restore whatever
    was there (a value or its absence) on every exit path, so a scoped
    choice can never masquerade as a user-set knob for a later run in the
    same process.  ``override=False`` leaves a value the user already set
    untouched; a falsy ``name`` sets nothing."""
    prior = os.environ.get(_ENV)
    if name and (override or prior is None):
        os.environ[_ENV] = str(name)
    try:
        yield
    finally:
        if prior is None:
            os.environ.pop(_ENV, None)
        else:
            os.environ[_ENV] = prior


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def block_ranges(segment_ids, n_blocks: int, bn: int, be: int,
                 n_eblocks: int):
    """Per-node-block [start, end) EDGE-BLOCK ranges for nondecreasing
    ``segment_ids`` (ops/fused_block.py schedules on them):
    block i's segments span rows [i*bn, (i+1)*bn), located by searchsorted,
    then converted to edge-block indices (floor start, ceil end)."""
    bounds = jnp.arange(n_blocks + 1, dtype=jnp.int32) * bn
    v = jnp.searchsorted(segment_ids, bounds, side="left")
    lo, hi = v[:-1], v[1:]
    start = (lo // be).astype(jnp.int32)
    end = jnp.minimum((-(-hi // be)).astype(jnp.int32), n_eblocks)
    return start, end
