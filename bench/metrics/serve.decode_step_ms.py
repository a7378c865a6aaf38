"""Median wall time of the executor's decode calls in the window, each
ending in the host's read of the next tokens (host clock)."""
from bench.harness import pctl


def read(run):
    if run.kind != "serve":
        return None
    w0, w1 = run.window
    d = [c.t1 - c.t0 for c in run.tx.calls
         if c.kind == "decode" and w0 <= c.t0 and c.t1 <= w1]
    return None if not d else 1e3 * pctl(d, 50)
