"""Device time of GAT's attention scores, in milliseconds a step.

Layer: the model step (``models/gnn/layers.py``: the a_src and a_dst
projections, the two (E, H) gathers of the scores and the LeakyReLU run
under ``jax.named_scope("attn_score")``, forward and backward). Source: the
device trace; the window's device seconds of ops whose scope path has the
component ``attn_score`` (``scope_seconds``), over the window's steps. A
program without the scope reads nothing.
"""


def read(run):
    seconds = run["scope_seconds"]("attn_score")
    if seconds <= 0 or not run["steps"]:
        return None
    return 1e3 * seconds / run["steps"]
