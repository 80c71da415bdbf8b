"""Process start to the opening of the window: drawing the weights,
building the engine, loading or compiling and warming up every step width,
and the traffic's ramp up to the window (closed loop: every client has its
first token; open loop: the lead)."""


def read(run):
    return run.setup_s
