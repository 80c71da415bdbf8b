"""95th percentile of every gap between consecutive tokens of one request
whose later token arrived inside the window, in milliseconds."""
from chipbench.stats import nearest_rank


def read(run):
    gaps = [b - a for r in run.records
            for a, b in zip(r["times"], r["times"][1:]) if run.in_window(b)]
    p = nearest_rank(gaps, 0.95)
    return None if p is None else 1000.0 * p
