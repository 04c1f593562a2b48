"""packed_docs — seeded documents of heavy-tailed length, as token ids.

What a code-model pre-training job reads after tokenisation: files whose
token counts are log-normal (median ``median_tokens``, ``sigma``; clipped
to [``min_tokens``, ``max_tokens``]), each a graph of its own to the
loader, so a step's 24 documents are packed by document and never cut.

Two streams of randomness, as in ``qm9_shaped``.  The LENGTH of every
document comes from ``layout_seed`` (fixed in the configuration file): the
buckets a step lands in, the padding and the attention's visible pairs are
then the same work for every ``--seed``.  The ids come from the run's
``--seed``: a Zipf(``zipf_a``) unigram over the ``vocab_size`` ids held
here (ranks mapped to ids by a seeded permutation), mixed with a seeded
first-order Markov table (with probability ``markov_mix`` a token is one of
its predecessor's ``FANOUT`` fixed successors), so that the next token is
partly predictable and a falling loss means something was learned.

``to_samples`` hands the loader what ``dataset_loading_and_splitting``
would: x = the id (exact in float32), node_y = [id, next id] with -1 where
a node has no successor in its graph, zero positions, no edges.
"""

from __future__ import annotations

import numpy as np

FANOUT = 4


def lengths(n: int, params: dict) -> np.ndarray:
    rng = np.random.default_rng([int(params.get("layout_seed", 0)), 0x1A])
    raw = rng.lognormal(np.log(float(params["median_tokens"])),
                        float(params["sigma"]), size=n)
    return np.clip(np.rint(raw), int(params["min_tokens"]),
                   int(params["max_tokens"])).astype(np.int32)


def generate(n: int, seed: int, params: dict) -> dict:
    """``n`` documents as flat arrays: ``n_tokens`` [n], ``ids`` [sum]."""
    vocab = int(params["vocab_size"])
    sizes = lengths(n, params)
    total = int(sizes.sum())
    rng = np.random.default_rng([int(seed), 0x70C])
    rank_p = np.arange(1, vocab + 1, dtype=np.float64) ** -float(
        params["zipf_a"])
    rank_p /= rank_p.sum()
    of_rank = rng.permutation(vocab).astype(np.int32)
    unigram = of_rank[rng.choice(vocab, size=total, p=rank_p)]
    successors = of_rank[rng.choice(vocab, size=(vocab, FANOUT), p=rank_p)]
    follow = rng.random(total) < float(params["markov_mix"])
    follow[np.concatenate([[0], np.cumsum(sizes)[:-1]])] = False
    which = rng.integers(FANOUT, size=total)
    ids = unigram.copy()
    # a followed token depends on its predecessor, which may be followed
    # too: runs of followed tokens are short (geometric), so a few
    # vectorised sweeps settle every one
    todo = np.flatnonzero(follow)
    while len(todo):
        ids[todo] = successors[ids[todo - 1], which[todo]]
        # a token is final once its predecessor is: keep those whose
        # predecessor was itself rewritten in this sweep
        todo = todo[np.isin(todo - 1, todo)]
    return {"n_tokens": sizes, "ids": ids}


def to_samples(corpus: dict, config: dict) -> list:
    from hydragnn_tpu.graph.batch import GraphSample

    if int(corpus["ids"].max()) >= int(config["vocab_size"]):
        raise ValueError("packed_docs: an id lies outside the held slice")
    samples, off = [], 0
    for n in corpus["n_tokens"].tolist():
        ids = corpus["ids"][off:off + n].astype(np.float32)
        off += n
        nxt = np.concatenate([ids[1:], np.float32([-1.0])])
        samples.append(GraphSample(
            x=ids[:, None], pos=np.zeros((n, 3), np.float32),
            node_y=np.stack([ids, nxt], axis=1)))
    return samples
