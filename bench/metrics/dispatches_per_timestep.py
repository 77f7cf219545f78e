"""Calls through the executor's and both model bundles' ``calls``
counters in the window, per executed timestep.  The counters count calls
at their own seams, so a fused verify counts once at the executor and
once per model bundle."""


def read(r):
    return r.dispatches / r.timesteps if r.timesteps else None
