"""95th percentile, over every request due inside the window, of the time
from its scheduled send to its first token.  A request with no first token
by the end of the drain counts as having it then (a lower bound: it is
missing, and the load generator's record counts it as failed)."""
from chipbench.stats import nearest_rank


def read(run):
    end = run.window[1] + run.traffic["drain_s"]
    due = [r for r in run.records
           if "scheduled" in r and run.in_window(r["scheduled"])]
    return nearest_rank([(r["times"][0] if r["times"] else end)
                         - r["scheduled"] for r in due], 0.95)
