"""Output tokens the clients received inside the window, per second of it."""


def read(run):
    n = sum(1 for r in run.records for t in r["times"] if run.in_window(t))
    return n / run.seconds
