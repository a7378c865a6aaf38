"""Share of the traced serving window in which no operation ran on the
device, in percent (profiler trace)."""


def read(run):
    if run.trace is None or run.kind != "serve":
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)
