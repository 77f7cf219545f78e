"""Mean wall-clock time of one executed engine timestep: the window over
the timesteps begun in it (host clock)."""


def read(r):
    return r.window_s / r.timesteps * 1e3 if r.timesteps else None
