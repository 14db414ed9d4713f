"""Operation counts from unpadded batch sizes."""
from types import SimpleNamespace as NS

import numpy as np
import pytest

import flops


def _sub():
    # seeds {0, 1}; hop 0 reaches 2, 3; hop 1 reaches 4; hop 2 reaches 5
    hop = lambda s, d: NS(src=np.array(s), dst=np.array(d))
    return NS(
        seeds=np.array([0, 1]),
        hops=[hop([0, 0, 1], [2, 3, 3]), hop([2, 3], [4, 0]), hop([4], [5])],
    )


def _cfg(kind):
    return {
        "model": {"kind": kind, "hidden": 8, "num_layers": 3, "num_heads": 2},
        "graph": {"feat_dim": 4, "num_classes": 3},
    }


def test_depth_sizes():
    assert flops.depth_sizes(_sub(), 3) == [2, 4, 5, 6]


def test_sage_step():
    # layer k computes rows within 2-k hops over the edges of hops 0..2-k
    rows, edges = [5, 4, 2], [6, 5, 3]
    dims = [4, 8, 8, 8]
    want = 0.0
    for k in range(3):
        passes = 2 if k == 0 else 3
        want += 2 * rows[k] * 2 * dims[k] * dims[k + 1] * passes
        want += edges[k] * dims[k] * (1 if k == 0 else 2)
    want += 3 * 2 * 2 * 8 * 3
    assert flops.train_step_flops(_sub(), _cfg("sage")) == pytest.approx(want)


def test_gat_step_counts_source_rows():
    # GAT transforms every source row of a needed edge: within 3-k hops
    src_rows, edges = [6, 5, 4], [6, 5, 3]
    dims = [4, 8, 8, 8]
    want = 0.0
    for k in range(3):
        passes = 2 if k == 0 else 3
        want += 2 * src_rows[k] * dims[k] * dims[k + 1] * passes
        want += 3 * (4 * src_rows[k] * 8 + edges[k] * (5 * 2 + 2 * 8))
    want += 3 * 2 * 2 * 8 * 3
    assert flops.train_step_flops(_sub(), _cfg("gat")) == pytest.approx(want)


def test_unknown_kind_is_an_error():
    with pytest.raises(ValueError, match="no reference for model kind"):
        flops.train_step_flops(_sub(), _cfg("no-such-kind"))
