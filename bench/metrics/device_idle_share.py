"""The share of the traced window in which no program ran on a chip, in
%, averaged over the cell's chips (profiler trace)."""


def read(r):
    if r.trace is None:
        return None
    t = r.trace
    return 100.0 * (1.0 - sum(t.busy_s) / len(t.busy_s) / t.window_s)
