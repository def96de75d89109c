"""The whole training step's share of the chip's bf16 peak, in percent.

Layer: the model step (``models/gnn/layers.py``, the jitted step). The
operations every step of the window requires (``bench/counts.py``, from the
true sizes of its block) over the window's length and the peak of
``bench/peaks.json``.
"""


def read(run):
    if not run["flops"]:
        return None
    rate = sum(run["flops"]) / run["window_s"]
    return 100.0 * rate / run["peaks"]["bf16_flops_per_s"]
