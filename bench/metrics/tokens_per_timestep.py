"""Output tokens streamed per executed engine timestep in the window
(host counters: the benchmark's token stream and its timestep count)."""


def read(r):
    return r.tokens / r.timesteps if r.timesteps else None
