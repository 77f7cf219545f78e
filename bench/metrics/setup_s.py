"""Process start to the window's open: imports, weights, compile or
compile-cache loads, warm-up and admitting the requests due at set-up
(host clock)."""


def read(r):
    return r.setup_s
