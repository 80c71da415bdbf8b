"""Mean host time of one ``Engine.step`` over the window: the step's wall
time less the time it spends blocked in its one device-to-host transfer
(``engine_wall_seconds_total`` - ``engine_device_wait_seconds_total``, over
``engine_steps_total``, each differenced between the window's opening and
closing).  The step is synchronous, so the device waits through this time.
None for a program without the device-wait counter."""


def read(run):
    o, c = run.counters_open, run.counters_close
    if "engine_device_wait_seconds_total" not in c:
        return None
    steps = c["engine_steps_total"] - o["engine_steps_total"]
    if steps <= 0:
        return None
    wall = c["engine_wall_seconds_total"] - o["engine_wall_seconds_total"]
    wait = (c["engine_device_wait_seconds_total"]
            - o["engine_device_wait_seconds_total"])
    return 1000.0 * (wall - wait) / steps
