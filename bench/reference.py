"""The plain reference: a Qwen2 decoder's forward pass in straightforward
``jax.numpy``, imported from nothing of the program.

Each layer: RMSNorm, Q/K/V projections with bias, rotary embedding on
the two halves of each head (``rotate_half``), causal grouped-query
softmax attention scaled by ``head_dim ** -0.5``, output projection and
residual; RMSNorm, SwiGLU MLP and residual.  Then a final RMSNorm and
the head (the embedding table where the model ties them).

``precision="float32"`` is the reference proper, in the precision the
configurations state: weights and activations in float32, every matmul
at the default precision (on a TPU one bfloat16 pass of each operand,
accumulated in float32), as a float32 serving path computes it.
``precision="bfloat16"`` is the control: the same pass with weights and
activations in bfloat16, norms and softmax accumulated in fp32 and
rounded back, the way a bf16 serving path would compute it.

The pass runs one sequence at a time and layer by layer on the device
that already holds the layer, so it fits beside the weights once the
program's caches are freed.  The head runs in blocks of rows and of
vocabulary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from model import Shape

PAD = 512          # sequences are padded to a multiple of this (causal)
ROWS = 128         # head: rows per block
VOCAB_BLOCK = 16384


def _dt(precision: str):
    return jnp.float32 if precision == "float32" else jnp.bfloat16


def _rms(x, scale, eps, dt):
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(dt)


def _rope(x, theta):
    """x [T, heads, hd], positions 0..T-1; fp32 angles."""
    t, _, hd = x.shape
    half = hd // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           -1).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("s", "precision"))
def _layer(stack, i, x, *, s: Shape, precision: str):
    """Layer ``i`` of a stacked tree over x [T, d]."""
    p = jax.tree.map(lambda w: w[i], stack[0])
    dt, pr = _dt(precision), lax.Precision.DEFAULT
    mx = jax.tree.map(lambda w: w.astype(dt), p["mixer"])
    f = jax.tree.map(lambda w: w.astype(dt), p["ffn"])
    t = x.shape[0]
    h = _rms(x, p["norm1"]["scale"], s.eps, dt)
    q = jnp.einsum("td,dnk->tnk", h, mx["w_q"], precision=pr) + mx["b_q"]
    k = jnp.einsum("td,dnk->tnk", h, mx["w_k"], precision=pr) + mx["b_k"]
    v = jnp.einsum("td,dnk->tnk", h, mx["w_v"], precision=pr) + mx["b_v"]
    q, k = _rope(q, s.theta), _rope(k, s.theta)
    rep = s.heads // s.kv_heads
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    sc = jnp.einsum("tnk,unk->ntu", q, k, precision=pr,
                    preferred_element_type=jnp.float32) * s.hd ** -0.5
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    sc = jnp.where(causal[None], sc, -jnp.inf)
    pr_ = jax.nn.softmax(sc, axis=-1).astype(dt)
    att = jnp.einsum("ntu,unk->tnk", pr_, v, precision=pr)
    x = x + jnp.einsum("tnk,nkd->td", att, mx["w_o"], precision=pr)
    h = _rms(x, p["norm2"]["scale"], s.eps, dt)
    g = jnp.einsum("td,df->tf", h, f["w_gate"], precision=pr)
    u = jnp.einsum("td,df->tf", h, f["w_up"], precision=pr)
    return x + jnp.einsum("tf,fd->td", jax.nn.silu(g) * u, f["w_down"],
                          precision=pr)


@functools.partial(jax.jit, static_argnames=("s", "precision"))
def _logits(head, x, *, s: Shape, precision: str):
    """Rows of x [R, d] through the final norm and the head: logits
    [R, V] in fp32, rounded to this precision's type.  The table is read
    in blocks of the vocabulary, so no copy of it is made whole."""
    dt, pr = _dt(precision), lax.Precision.DEFAULT
    h = _rms(x, head["final_norm"]["scale"], s.eps, dt)
    table = head["embed" if s.tied else "lm_head"]["table"]
    blocks = []
    for lo in range(0, s.vocab, VOCAB_BLOCK):
        w = table[lo:lo + VOCAB_BLOCK].astype(dt)
        lg = jnp.einsum("rd,vd->rv", h, w, precision=pr,
                        preferred_element_type=jnp.float32)
        blocks.append(lg.astype(dt).astype(jnp.float32))
    return jnp.concatenate(blocks, -1)


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
    s = w.shape
    t = len(tokens)
    padded = np.zeros(-(-t // PAD) * PAD, np.int32)
    padded[:t] = tokens
    table = w.params["embed"]["table"]
    x = jnp.take(table, jnp.asarray(padded), axis=0).astype(_dt(precision))
    for dev, stack in w.stages:
        x = jax.device_put(x, dev)
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            x = _layer(stack, i, x, s=s, precision=precision)
    return x


def logits(w, x, precision: str):
    """Logits [R, V] (fp32 values of this precision's pass) of the rows
    of x [R, d], in blocks of rows, on the head's device."""
    dev = next(iter(w.params["final_norm"]["scale"].devices()))
    head = {k: w.params[k] for k in ("final_norm", "embed", "lm_head")
            if k in w.params}
    out = []
    for lo in range(0, x.shape[0], ROWS):
        xs = jax.device_put(x[lo:lo + ROWS], dev)
        out.append(_logits(head, xs, s=w.shape, precision=precision))
    return jnp.concatenate(out)


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
