"""Share of the window the training loop spent waiting for a plan.

Layer: the train loop (``train/trainer.py``). Source: the program's
``step/wait`` spans (time ``train_epoch`` blocks on the plan source),
summed over the window, over the window's length.
"""


def read(run):
    waits = [s for s in run["spans"] if s["name"] == "step/wait" and s["main"]]
    if not waits:
        return None
    t0, t1 = run["t0"], run["t1"]
    total = sum(min(s["t1"], t1) - max(s["t0"], t0) for s in waits)
    return 100.0 * total / (t1 - t0)
