"""``train_graphs_per_s`` is the median whole epoch's rate: every number
below is worked out by hand in the comment beside it."""

import pytest

from benchload import load

drv = load("drivers", "train_epochs")


def _epochs(seconds, graphs=1000):
    out, t = [], 10.0
    for s in seconds:
        out.append({"graphs": graphs, "t0": t, "t1": t + s})
        t += s
    return out


@pytest.mark.parametrize("seconds,want", [
    ([4.0] * 6, 250.0),                       # six equal epochs: 1000 / 4
    ([4.0, 4.0, 5.5, 4.0, 4.0, 4.0], 250.0),  # one stalled boundary of six
    ([4.0, 9.0, 4.0, 8.0, 4.0], 250.0),       # two of five
    # three kinds of epoch: the middle pair, (1000/4 + 1000/5) / 2
    ([2.0, 8.0, 4.0, 5.0, 2.0, 5.0], 225.0),
    ([5.0], 200.0),
])
def test_median_epoch_rate(seconds, want):
    assert drv.median_epoch_rate(_epochs(seconds)) == pytest.approx(want)


def test_no_whole_epoch_no_rate():
    assert drv.median_epoch_rate([]) is None
