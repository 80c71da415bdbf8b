"""Front-end time per engine step over the window: the HTTP engine worker's
time on submits, aborts and event fan-out outside ``Engine.step``
(``engine_frontend_seconds_total``) over ``engine_steps_total``, both
differenced between the window's opening and closing.  None for a program
without the front-end counter."""


def read(run):
    o, c = run.counters_open, run.counters_close
    if "engine_frontend_seconds_total" not in c:
        return None
    steps = c["engine_steps_total"] - o["engine_steps_total"]
    if steps <= 0:
        return None
    front = (c["engine_frontend_seconds_total"]
             - o["engine_frontend_seconds_total"])
    return 1000.0 * front / steps
