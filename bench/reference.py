"""The plain reference: each model's forward pass in straightforward
``jax.numpy``, imported from nothing of the program.

The layers' arithmetic is the model family's (``hidden`` and ``head`` of
``bench/families/<model_type>.py``); this module pads a sequence, runs
the head in blocks of rows, compares logit rows and switches the
precision.

``precision="float32"`` is the reference proper, in the precision the
configurations state: weights and activations in float32, every matmul
at the default precision (on a TPU one bfloat16 pass of each operand,
accumulated in float32), as a float32 serving path computes it.
``precision="bfloat16"`` is the control: the same pass with weights and
activations in bfloat16, norms and softmax accumulated in fp32 and
rounded back, the way a bf16 serving path would compute it.

The pass runs one sequence at a time and layer by layer on the device
that already holds the layer, so it fits beside the weights once the
program's caches are freed.  The head runs in blocks of rows (and the
family's head in blocks of vocabulary).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

PAD = 512          # sequences are padded to a multiple of this (causal)
ROWS = 128         # head: rows per block


def dtype(precision: str):
    """The weights' and activations' type of a precision: float32 for the
    reference, bfloat16 for the control."""
    return jnp.float32 if precision == "float32" else jnp.bfloat16


@jax.jit
def _compare(ref, got):
    """Per row of [R, V] logits: the largest absolute gap, the gap's
    norm over the reference's, and KL(softmax(ref) || softmax(got)) in
    nats, worked as log(sum p exp(e)) - sum p e over e = got - ref, which
    keeps its digits where the two nearly agree."""
    e = got - ref
    p = jax.nn.softmax(ref, axis=-1)
    kl = jnp.log1p(jnp.sum(p * jnp.expm1(e), -1)) - jnp.sum(p * e, -1)
    rel = jnp.sqrt(jnp.sum(e * e, -1) / jnp.sum(ref * ref, -1))
    return jnp.max(jnp.abs(e), -1), rel, kl


STATS = ("abs_err", "rel_err", "kl")


def hidden(w, tokens, precision: str):
    """Final hidden states [T, d] of one token sequence (padded to PAD
    rows; rows past ``len(tokens)`` are padding)."""
    t = len(tokens)
    padded = np.zeros(-(-t // PAD) * PAD, np.int32)
    padded[:t] = tokens
    return w.family.hidden(w, padded, precision)


def logits(w, x, precision: str):
    """Logits [R, V] (fp32 values of this precision's pass) of the rows
    of x [R, d], in blocks of rows, on the head's device."""
    return jnp.concatenate([w.family.head(w, x[lo:lo + ROWS], precision)
                            for lo in range(0, x.shape[0], ROWS)])


def compare(ref, got) -> dict:
    """``STATS`` of each row of ``got`` against ``ref`` ([R, V] each;
    ``got`` goes to ``ref``'s device)."""
    dev = next(iter(ref.devices()))
    out = {k: [] for k in STATS}
    for lo in range(0, ref.shape[0], ROWS):
        vals = _compare(ref[lo:lo + ROWS],
                        jax.device_put(got[lo:lo + ROWS], dev))
        for k, v in zip(STATS, vals):
            out[k].append(np.asarray(v, np.float64))
    return {k: np.concatenate(v) for k, v in out.items()}


def check_rows(w, prompt, served, rows: dict, *, control: bool = False):
    """The program's logit rows of one request against the reference.

    ``rows`` maps k to the program's logits [V] that predicted served
    token k (the last prompt position for k = 0).  The reference runs
    once over the prompt and the served tokens.  Returns ``STATS`` per
    row, and ``tokens_ok``: whether each served token is the row's own
    largest logit (greedy).  With ``control``, also the ``STATS`` of the
    bfloat16 pass's logits at the same positions, under ``control_``."""
    prompt, served = np.asarray(prompt), np.asarray(served)
    ks = np.array(sorted(k for k in rows if k < len(served)), np.int64)
    if ks.size == 0:
        return None
    seq = np.concatenate([prompt, served[:-1]])
    pos = jnp.asarray(len(prompt) - 1 + ks)
    got = np.stack([rows[int(k)] for k in ks])
    ref = logits(w, hidden(w, seq, "float32")[pos], "float32")
    out = compare(ref, got)
    out["tokens_ok"] = got.argmax(-1) == served[ks]
    if control:
        ctrl = logits(w, hidden(w, seq, "bfloat16")[pos], "bfloat16")
        out.update({f"control_{k}": v for k, v in compare(ref, ctrl).items()})
    return out
