"""JAX traces (jit cache misses) inside ``Engine.step`` during the window:
``engine_compiles_total`` differenced between the window's opening and
closing.  Warm-up compiles every width the traffic uses, so it should read
0.  None for a program without the counter."""


def read(run):
    name = "engine_compiles_total"
    if name not in run.counters_close:
        return None
    return int(run.counters_close[name] - run.counters_open[name])
