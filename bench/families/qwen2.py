"""The Qwen2 family (``model_type`` "qwen2"): a dense decoder with
grouped-query attention, QKV bias and a SwiGLU MLP.

What the harness asks of a family (``model.family``): ``Shape``,
``make_local``, ``program_config``, the reference's ``hidden`` and
``head``, and the counts ``verify_call`` and ``prefill_flops``.

The reference layer: RMSNorm, Q/K/V projections with bias, rotary
embedding on the two halves of each head (``rotate_half``), causal
grouped-query softmax attention scaled by ``head_dim ** -0.5``, output
projection and residual; RMSNorm, SwiGLU MLP and residual.  Then a
final RMSNorm and the head (the embedding table where the model ties
them).  Weights are made from the seed, on the device, in the parameter
layout ``repro.models.transformer`` serves from; the reference reads the
same arrays.
"""
from __future__ import annotations

import dataclasses
import functools
import sys

import jax
import jax.numpy as jnp
from jax import lax

from model import Weights
from reference import dtype

VOCAB_BLOCK = 16384     # head: vocabulary rows per block
F32 = 4


@dataclasses.dataclass(frozen=True)
class Shape:
    """One dense Qwen2-style decoder as published (widths never cut)."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    ff: int
    vocab: int
    eps: float
    theta: float
    tied: bool

    @property
    def hd(self) -> int:
        return self.d // self.heads

    @classmethod
    def from_json(cls, c: dict) -> "Shape":
        return cls(layers=c["num_hidden_layers"], d=c["hidden_size"],
                   heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   ff=c["intermediate_size"], vocab=c["vocab_size"],
                   eps=float(c["rms_norm_eps"]), theta=float(c["rope_theta"]),
                   tied=bool(c["tie_word_embeddings"]))


def program_config(s: Shape, name: str):
    """The program's ``ModelConfig`` for a shape (fp32, dense, QKV bias,
    SwiGLU: the Qwen2 block)."""
    from repro.models.config import ModelConfig
    return ModelConfig(name=name, family="dense", num_layers=s.layers,
                       d_model=s.d, num_heads=s.heads,
                       num_kv_heads=s.kv_heads, d_ff=s.ff,
                       vocab_size=s.vocab, mlp_variant="swiglu",
                       qkv_bias=True, tie_embeddings=s.tied, norm_eps=s.eps,
                       rope_theta=s.theta, dtype="float32")


# -- weights ---------------------------------------------------------------

def _normal(key, shape, scale):
    return jax.random.normal(key, shape, jnp.float32) * scale


def init_head(key, s: Shape):
    """Embedding, final norm and (untied) output head."""
    k = jax.random.split(key, 3)
    out = {"embed": {"table": _normal(k[0], (s.vocab, s.d), 0.02)},
           "final_norm": {"scale": 1.0 + _normal(k[1], (s.d,), 0.1)}}
    if not s.tied:
        out["lm_head"] = {"table": _normal(k[2], (s.vocab, s.d), 0.02)}
    return out


def init_layers(key, s: Shape, n: int):
    """``n`` decoder layers stacked on a leading axis, as the program's
    ``params["stack"]`` holds them (a list of one unit).  Biases and norm
    scales are random too, so the reference checks that they are read."""
    d, h, kv, hd, ff = s.d, s.heads, s.kv_heads, s.hd, s.ff
    k = jax.random.split(key, 11)
    mixer = {
        "w_q": _normal(k[0], (n, d, h, hd), d ** -0.5),
        "w_k": _normal(k[1], (n, d, kv, hd), d ** -0.5),
        "w_v": _normal(k[2], (n, d, kv, hd), d ** -0.5),
        "w_o": _normal(k[3], (n, h, hd, d), (h * hd) ** -0.5),
        "b_q": _normal(k[4], (n, h, hd), 0.1),
        "b_k": _normal(k[5], (n, kv, hd), 0.1),
        "b_v": _normal(k[6], (n, kv, hd), 0.1),
    }
    ffn = {"w_gate": _normal(k[7], (n, d, ff), d ** -0.5),
           "w_up": _normal(k[8], (n, d, ff), d ** -0.5),
           "w_down": _normal(k[9], (n, ff, d), ff ** -0.5)}
    norms = _normal(k[10], (2, n, d), 0.1) + 1.0
    return [{"norm1": {"scale": norms[0]}, "mixer": mixer,
             "norm2": {"scale": norms[1]}, "ffn": ffn}]


def init_model(key, s: Shape):
    """A whole model on one device: ``{"embed", "final_norm",
    ["lm_head"], "stack"}``."""
    kh, kl = jax.random.split(key)
    return {**init_head(kh, s), "stack": init_layers(kl, s, s.layers)}


def make_local(seed_k, s: Shape, device) -> Weights:
    """The whole model on ``device``, made by one compiled program."""
    sharding = jax.sharding.SingleDeviceSharding(device)
    params = jax.jit(init_model, static_argnums=1,
                     out_shardings=sharding)(seed_k, s)
    return Weights(sys.modules[__name__], s, params,
                   [(device, params["stack"])])


# -- the reference -----------------------------------------------------------

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
    dt, pr = dtype(precision), lax.Precision.DEFAULT
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
    dt, pr = dtype(precision), lax.Precision.DEFAULT
    h = _rms(x, head["final_norm"]["scale"], s.eps, dt)
    table = head["embed" if s.tied else "lm_head"]["table"]
    blocks = []
    for lo in range(0, s.vocab, VOCAB_BLOCK):
        w = table[lo:lo + VOCAB_BLOCK].astype(dt)
        lg = jnp.einsum("rd,vd->rv", h, w, precision=pr,
                        preferred_element_type=jnp.float32)
        blocks.append(lg.astype(dt).astype(jnp.float32))
    return jnp.concatenate(blocks, -1)


def hidden(w: Weights, tokens, precision: str):
    """Final hidden states [T, d] of the token ids ``tokens`` [T]: the
    embedding, then each layer on the device that holds it."""
    table = w.params["embed"]["table"]
    x = jnp.take(table, jnp.asarray(tokens), axis=0).astype(dtype(precision))
    for dev, stack in w.stages:
        x = jax.device_put(x, dev)
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            x = _layer(stack, i, x, s=w.shape, precision=precision)
    return x


def head(w: Weights, x, precision: str):
    """Logits [R, V] of the rows x [R, d], on the head's device."""
    dev = next(iter(w.params["final_norm"]["scale"].devices()))
    params = {k: w.params[k] for k in ("final_norm", "embed", "lm_head")
              if k in w.params}
    return _logits(params, jax.device_put(x, dev), s=w.shape,
                   precision=precision)


# -- operations and bytes (``flops.py`` states what is counted) ---------------

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
