"""Percent of the window spent outside the executor's prefill and decode
calls: the scheduler's and the engine's host work (host clock)."""


def read(run):
    if run.kind != "serve":
        return None
    w0, w1 = run.window
    inside = sum(max(0.0, min(c.t1, w1) - max(c.t0, w0))
                 for c in run.tx.calls)
    return 100.0 * (1.0 - inside / (w1 - w0))
