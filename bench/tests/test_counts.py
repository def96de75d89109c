"""Operations of a small block, counted by hand."""
import numpy as np
import pytest

from bench import counts

# two layer transitions: into the 2 targets from 3 vertices over 4 edges, and
# into those 3 from 5 vertices over 6 edges
BLOCK = {
    "frontiers": [np.arange(2), np.arange(3), np.arange(5)],
    "layers": [(np.zeros(4), np.zeros(4)), (np.zeros(6), np.zeros(6))],
}
SAGE = {"model": "sage", "num_layers": 2, "feat_dim": 3, "hidden_dim": 4,
        "num_classes": 2, "num_heads": 1}


def test_block_sizes():
    assert counts.block_sizes(BLOCK) == [
        {"n_dst": 2, "n_src": 3, "edges": 4}, {"n_dst": 3, "n_src": 5, "edges": 6}]


def test_sage_step_flops_by_hand():
    # input layer (3 -> 4 over 6 edges into 3 rows), no input gradient:
    # sum 6*3 + mean 3*3, two matmuls 2*2*3*3*4, bias 12, relu 12;
    # backward: weight grads 144, bias 12, relu 12
    first = (18 + 9 + 144 + 12 + 12) + (144 + 12 + 12)
    # last layer (4 -> 2 over 4 edges into 2 rows): forward 16 + 8 + 64 + 4;
    # backward 64 + 4 and the input gradient 64 + 24
    last = (16 + 8 + 64 + 4) + (64 + 4 + 64 + 24)
    loss = 3 * 5 * 2 * 2
    sizes = counts.block_sizes(BLOCK)
    assert counts.step_flops(SAGE, sizes) == first + last + loss == 671


def test_other_models_are_refused():
    with pytest.raises(ValueError, match="bench/models/no_such_model.py"):
        counts.step_flops(dict(SAGE, model="no_such_model"), counts.block_sizes(BLOCK))
