"""The one device-scope helper: a ``jax.named_scope`` under a declared name.

Every op traced inside carries ``name`` in its HLO ``op_name``, so the
device trace can be split by it (docs/TELEMETRY.md "Tracing").  The step
builders (train/trainer.py, parallel/) take their phases from here, and so
do the layers that name their own parts (models/laguna.py, ops/attention.py,
ops/moe.py); the names are declared in analysis/registry.py SCOPE_NAMES
(lint REG006).  Metadata only: the executed program is the same with or
without it.
"""

from __future__ import annotations

import jax


def phase(name: str):
    return jax.named_scope(name)
