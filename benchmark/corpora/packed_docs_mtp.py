"""packed_docs_mtp — ``packed_docs`` with a second label column.

The documents are ``packed_docs``'s own (that file loaded by path and left
as it stands: lengths from ``layout_seed``, ids from ``--seed``); only
``to_samples`` differs: node_y = [id, next id, id after next], -1 where a
node has no such successor in its graph.  The third column is what a
multi-token-prediction head is trained against.
"""

from __future__ import annotations

import importlib.util
import os

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "benchmark_corpora_packed_docs_for_mtp", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "packed_docs.py"))
_docs = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_docs)

lengths = _docs.lengths
generate = _docs.generate


def to_samples(corpus: dict, config: dict) -> list:
    samples = _docs.to_samples(corpus, config)
    for s in samples:
        ids = s.node_y[:, 0]
        after = np.concatenate(
            [ids[2:], np.full(min(2, len(ids)), -1.0, np.float32)])
        s.node_y = np.concatenate([s.node_y, after[:, None]], axis=1)
    return samples
