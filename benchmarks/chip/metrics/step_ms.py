"""Mean host wall time of one ``Engine.step`` over the window: the engine
registry's ``engine_wall_seconds_total`` over ``engine_steps_total``, both
differenced between the window's opening and closing.  Each step ends in
the engine's one blocking device-to-host transfer, so this is the whole
step, device time included."""


def read(run):
    o, c = run.counters_open, run.counters_close
    steps = c["engine_steps_total"] - o["engine_steps_total"]
    if steps <= 0:
        return None
    wall = c["engine_wall_seconds_total"] - o["engine_wall_seconds_total"]
    return 1000.0 * wall / steps
