"""A configuration file, its model families, and the weights they make.

A configuration file (``bench/configs/<name>.json``) holds the target's
published ``config.json`` keys at its top level, the draft's under
``draft`` and the serving settings under ``serving``.  Each model's
published ``model_type`` names its family: the module
``bench/families/<model_type>.py``, which builds, serves, checks and
prices that architecture.  A family gives:

- ``Shape``: the benchmark's own view of one model (frozen, hashable,
  ``Shape.from_json(keys)``, with at least ``layers``, ``d`` and
  ``vocab``); the reference and the counts read it, never the
  program's config;
- ``make_local(seed_key, shape, device) -> Weights``: weights made from
  the seed on the device, in the layout the program serves from, as
  much of each layer as one chip holds;
- ``program_config(shape, name)``: the program's ``ModelConfig``;
- ``hidden(weights, tokens, precision)`` and ``head(weights, x,
  precision)``: the plain reference's layer walk and its final norm and
  head (``reference.py`` pads, blocks the rows and compares);
- ``verify_call(shape, layers, nodes, context, kv_rows) -> (flops,
  bytes)`` and ``prefill_flops(shape, n)`` (``flops.py``).

So a new architecture is a configuration file and a family module.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import types

import jax
import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
# directories searched, in order, for <model_type>.py
FAMILIES = [os.path.join(BENCH, "families")]


def family(c: dict, where: str) -> types.ModuleType:
    """The family module of one model's ``config.json`` keys ``c``: the
    first ``<model_type>.py`` on ``FAMILIES``.  ``where`` names the keys
    in an error (a file, and whether the draft's)."""
    name = c.get("model_type")
    if name is None:
        raise LookupError(f"{where}: no \"model_type\" key, so no "
                          f"bench/families/<model_type>.py to look for")
    if not isinstance(name, str) or not name.isidentifier():
        raise LookupError(f"{where}: model_type {name!r} names no module")
    paths = [os.path.join(d, f"{name}.py") for d in FAMILIES]
    path = next((p for p in paths if os.path.isfile(p)), None)
    if path is None:
        raise LookupError(f"{where}: model_type {name!r} has no family "
                          f"module; looked for {', '.join(paths)}")
    modname = f"bench_family_{name}"
    mod = sys.modules.get(modname)
    if mod is None or mod.__file__ != path:
        spec = importlib.util.spec_from_file_location(modname, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[modname] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[modname]
            raise
    return mod


def families(conf: dict, where: str = "the configuration"):
    """(target family, draft family) of a configuration file."""
    return family(conf, where), family(conf["draft"], f"{where} [draft]")


def load_config(name: str) -> dict:
    """``bench/configs/<name>.json``; a model whose family is missing or
    unknown fails here, naming the file."""
    path = os.path.join(BENCH, "configs", f"{name}.json")
    with open(path) as f:
        conf = json.load(f)
    families(conf, path)
    return conf


def shapes(conf: dict):
    """(target Shape, draft Shape) of a configuration file, each its
    family's."""
    return tuple(fam.Shape.from_json(c) for fam, c in
                 zip(families(conf), (conf, conf["draft"])))


def seed_key(seed: int):
    """A JAX key from any non-negative seed (wider than 32 bits too)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


@dataclasses.dataclass
class Weights:
    """The benchmark's weights of one model and where they live.

    ``family`` is the module that made them (``model.family``), and
    ``shape`` its ``Shape``.  ``params`` is what the program is handed.
    ``stages`` lists, per device, that device's layers (a stack tree with
    a leading layer axis), so the reference runs layer by layer where the
    layers already are (one entry on one chip; one per stage for a
    pipeline placement)."""

    family: types.ModuleType
    shape: object
    params: dict
    stages: list


def nbytes(tree) -> int:
    return int(sum(np.prod(x.shape) * x.dtype.itemsize
                   for x in jax.tree.leaves(tree)))
