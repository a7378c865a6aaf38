"""Per step, device time in collective operations during which no other
operation runs on that device, averaged over the chips (profiler trace).
Nothing to read on one chip."""


def read(run):
    if run.trace is None or run.kind != "train" or run.chips < 2:
        return None
    steps = sum(1 for name, _, _ in run.trace.host if name == "bench.step")
    if not steps:
        return None
    return 1e3 * run.trace.exposed_collective_s() / steps
