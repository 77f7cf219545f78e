"""Operations and bytes that the served programs need, from the shapes
of each call, and the chips' peaks (``peaks.json``).

Counts are what the algorithm requires, not what XLA's cost model says
of the program under test: a matmul of an [m, k] by a [k, n] operand is
2mkn operations; a weight is read once per call; a key/value row is read
once per row of the batch that attends to it; logits are written once.
"""
from __future__ import annotations

import json
import os

from model import Shape

F32 = 4


def peaks(device_kind: str) -> dict:
    """{"bf16_flops": FLOP/s, "hbm_bytes": bytes/s} of one chip; an
    unknown device is an error, never a default."""
    with open(os.path.join(os.path.dirname(__file__), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to bench/peaks.json with its source")
    return table[device_kind]


def layer_weights(s: Shape) -> int:
    """Matmul weights of one decoder layer (elements)."""
    return (s.d * s.heads * s.hd + 2 * s.d * s.kv_heads * s.hd
            + s.heads * s.hd * s.d + 3 * s.d * s.ff)


def layer_bytes(s: Shape) -> int:
    """Every fp32 weight of one decoder layer, biases and norms included."""
    return F32 * (layer_weights(s) + (s.heads + 2 * s.kv_heads) * s.hd
                  + 2 * s.d)


def head_bytes(s: Shape) -> int:
    return F32 * (s.vocab * s.d + s.d)


def kv_row_bytes(s: Shape, layers: int) -> int:
    """Keys and values of one position over ``layers`` layers."""
    return F32 * 2 * s.kv_heads * s.hd * layers


def token_flops(s: Shape, layers: int, context: float, head: bool) -> float:
    """One token through ``layers`` layers attending to ``context``
    positions, plus the head when ``head``."""
    f = layers * (2 * layer_weights(s) + 4 * s.heads * s.hd * context)
    return f + (2 * s.d * s.vocab if head else 0)


def prefill_flops(s: Shape, n: int) -> float:
    """A prompt of n tokens (causal: token i sees i + 1 positions); the
    head runs on the last position only."""
    return (s.layers * (2 * layer_weights(s) * n
                        + 4 * s.heads * s.hd * n * (n + 1) / 2)
            + 2 * s.d * s.vocab)


def verify_call(s: Shape, layers: int, nodes: int, context: float,
                kv_rows: float):
    """(operations, bytes) of one tree-verify call on ``nodes`` valid
    tree nodes through ``layers`` layers and the head.  ``context`` is
    the sum over the nodes of the positions each attends to; ``kv_rows``
    the key/value rows read and written (each slot row's committed
    prefix once, the tree rows, the new nodes)."""
    flops = (nodes * token_flops(s, layers, 0, True)
             + layers * 4 * s.heads * s.hd * context)
    nbytes = (layers * layer_bytes(s) + head_bytes(s)
              + kv_row_bytes(s, layers) * kv_rows + F32 * nodes * s.vocab)
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """The roofline's least time of one call, in seconds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes"])
