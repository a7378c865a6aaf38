"""Output tokens delivered inside the window over its length (serving)."""


def read(run):
    if run.kind != "serve":
        return None
    return run.stats["tokens"] / run.seconds
