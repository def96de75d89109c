"""Device time of GAT's edge softmax, in milliseconds a step.

Layer: the model step (``models/gnn/layers.py``: the per-destination
softmax of the (E, H) attention logits runs under
``jax.named_scope("softmax")``, forward and backward). Source: the device
trace; the window's device seconds of ops whose scope path has the
component ``softmax`` (``scope_seconds``), over the window's steps.
"""


def read(run):
    seconds = run["scope_seconds"]("softmax")
    if seconds <= 0 or not run["steps"]:
        return None
    return 1e3 * seconds / run["steps"]
