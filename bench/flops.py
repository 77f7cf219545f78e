"""Operations and bytes that the served programs need, and the chips'
peaks (``peaks.json``).

The counts of each call come from its shapes, by the model family's
``verify_call`` and ``prefill_flops`` (``bench/families/``).  They are
what the algorithm requires, not what XLA's cost model says of the
program under test: a matmul of an [m, k] by a [k, n] operand is 2mkn
operations; a weight is read once per call; a key/value row is read
once per row of the batch that attends to it; logits are written once.
"""
from __future__ import annotations

import json
import os


def peaks(device_kind: str) -> dict:
    """{"bf16_flops": FLOP/s, "hbm_bytes": bytes/s} of one chip; an
    unknown device is an error, never a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to bench/peaks.json with its source")
    return table[device_kind]


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline's least time of one call, in seconds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes"])
