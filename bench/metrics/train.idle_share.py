"""Share of the traced training window in which no operation ran on the
device, averaged over the chips, in percent (profiler trace)."""


def read(run):
    if run.trace is None or run.kind != "train":
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)
