"""95th percentile of every gap between consecutive streamed tokens of
one request, both inside the window (in a traced run, inside its part
after the trace), over all requests (host clock).  The time between
tokens as a tail: per layer, since a host stall of a few seconds meets
every request of a closed loop at once (PERF.md)."""
from serve import percentile


def read(r):
    return percentile(r.tbt_ms, 95)
