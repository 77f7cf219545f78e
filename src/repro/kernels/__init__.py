"""Pallas TPU kernels (``flash``, ``tree_block``, ``paged``, ``quant``),
their jnp oracles (``ref``) and the dispatchers (``ops``).

Interpret mode follows the platform: a kernel runs the Pallas interpreter
only where the default backend is not a TPU, and never on one.
"""
from __future__ import annotations

from typing import Optional

import jax


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas call runs in interpret mode.

    ``None`` (every caller on the serving path) decides from the platform
    at trace time: interpret off the TPU, compile on it.  ``False`` forces
    the Mosaic lowering off the TPU too, which is how a kernel is compiled
    for a described, unattached chip.  Asking for the interpreter on a TPU
    is an error, so no call on the chip can quietly interpret.
    """
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode is never used on a TPU")
    return bool(interpret)
