"""Operations a step requires, from the true sizes of its block.

A block is one mini-batch sampled as a whole (``batch_size`` targets,
deduplicated frontiers, no padding): per layer transition ``k`` (into depth
``k``, 0 = targets) the number of destination vertices ``n_dst``, source
vertices ``n_src`` and sampled edges ``edges``. The counts are the same
whatever implements a layer: a split plan's halo rows, data parallelism's
redundant rows and a kernel's padded tiles do not count.

The layers are counted by the configuration's model, ``bench/models/<model>.py``
(``step_flops``); the loss is counted here. Conventions:

* A multiply-add is 2 operations; an add, compare, exp or divide is 1.
* The backward pass of each operation costs twice its forward (one product
  for each operand), except what only the input features' gradient would
  need: the input layer never computes it.
"""
from __future__ import annotations


def block_sizes(block: dict) -> list[dict]:
    """``[{"n_dst", "n_src", "edges"}]`` per layer transition of a block in
    the form ``reference.block_arrays`` takes."""
    fr = block["frontiers"]
    return [
        {"n_dst": len(fr[k]), "n_src": len(fr[k + 1]), "edges": len(src)}
        for k, (src, _) in enumerate(block["layers"])
    ]


def layers(cfg: dict, sizes: list[dict]):
    """``(size, d_in, d_out, is_input, is_last)`` per layer, input layer
    first."""
    from bench.reference import layer_dims

    dims = layer_dims(cfg)
    L = len(dims)
    for j, (d_in, d_out) in enumerate(dims):
        yield sizes[L - 1 - j], d_in, d_out, j == 0, j == L - 1


def step_flops(cfg: dict, sizes: list[dict]) -> float:
    """Operations of one training step (forward, loss and backward)."""
    from bench import registry

    total = registry.load("models", cfg["model"]).step_flops(cfg, sizes)
    n_t = sizes[0]["n_dst"]
    c = int(cfg["num_classes"])
    total += 3 * 5 * n_t * c  # log-softmax, pick and their gradient
    return float(total)
