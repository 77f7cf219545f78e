"""The whole step's share of the chips' bf16 peak, in %: the operations
that the window's verified tree nodes, the draft's proposals and the
prefilled prompt tokens require (``flops.py``, from shapes), over the
window and over chips x peak."""


def read(r):
    if r.window_s <= 0:
        return None
    return 100.0 * r.useful_flops / r.window_s / (r.chips
                                                  * r.peak["bf16_flops"])
