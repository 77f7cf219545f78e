"""The target's fused tree-verify program against its roofline, in %:
the least time of a call (the larger of bytes over HBM bandwidth and
operations over peak, from each call's shapes), averaged over the
target's verify calls in the traced window, over the device time of a
program launched by the target's ``tree_verify_rows``, averaged over
those the trace pairs with their launch spans (``trace.pair_launches``).
Nothing to read where the trace pairs no such program."""

LAUNCH = "target_tree_verify_rows"


def read(r):
    t = r.trace
    if t is None or not t.launched_s.get(LAUNCH) or \
            not r.least_calls.get("target"):
        return None
    least = r.least_s["target"] / r.least_calls["target"]
    return 100.0 * least / (t.launched_s[LAUNCH] / t.launched_calls[LAUNCH])
