"""Output tokens streamed in the window (each counted when the engine
streams it), over the window's seconds (host clock)."""


def read(r):
    return r.tokens / r.window_s
