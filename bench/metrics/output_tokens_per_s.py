"""Output tokens that reached the host in the window, over its seconds."""


def read(run):
    n = sum(1 for r in run.requests for t in r.stamps if run.in_window(t))
    return n / run.seconds if n else None
