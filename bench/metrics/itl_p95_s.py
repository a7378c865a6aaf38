"""95th percentile of every gap between consecutive output tokens of one
request whose later token lands in the window (serving)."""
from bench.harness import pctl


def read(run):
    if run.kind != "serve":
        return None
    return pctl(run.stats["gaps"], 95)
