"""A configuration file, turned into shapes, seeded weights and the
program's ``ModelConfig``.

A configuration file (``bench/configs/<name>.json``) holds the target's
published ``config.json`` keys at its top level, the draft's under
``draft`` and the serving settings under ``serving``.  ``Shape`` is the
benchmark's own view of one model; the reference (``reference.py``) and
the FLOP counts (``flops.py``) read it, never the program's config.

Weights are made here, from the seed, on the device, in the parameter
layout ``repro.models.transformer`` serves from.  The reference reads the
same arrays: they are the benchmark's, not the program's.
"""
from __future__ import annotations

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))


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


def load_config(name: str) -> dict:
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def shapes(conf: dict):
    """(target Shape, draft Shape) of a configuration file."""
    return Shape.from_json(conf), Shape.from_json(conf["draft"])


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


def seed_key(seed: int):
    """A JAX key from any non-negative seed (wider than 32 bits too)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


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


@dataclasses.dataclass
class Weights:
    """The benchmark's weights of one model and where they live.

    ``params`` is what the program is handed.  ``stages`` lists, per
    device, that device's layers (a stack tree with a leading layer
    axis), so the reference runs layer by layer where the layers already
    are (one entry on one chip; one per stage for a pipeline placement)."""

    shape: Shape
    params: dict
    stages: list


def make_local(seed_k, s: Shape, device) -> Weights:
    """The whole model on ``device``, made by one compiled program."""
    sharding = jax.sharding.SingleDeviceSharding(device)
    params = jax.jit(init_model, static_argnums=1,
                     out_shardings=sharding)(seed_k, s)
    return Weights(s, params, [(device, params["stack"])])


def nbytes(tree) -> int:
    return int(sum(np.prod(x.shape) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree)))
