"""Tokens trained over the whole mesh inside the window, over the time
from the window's start to the end of its last step (training)."""


def read(run):
    if run.kind != "train" or not run.out["steps"]:
        return None
    t0, t1 = run.window
    return len(run.out["steps"]) * run.out["tokens_per_step"] / (t1 - t0)
