"""The one traffic generator: a mix file (``bench/traffic/<name>.json``)
in, a seeded schedule of requests out.

Every seed gets the same set of sizes and gaps: prompt and output
lengths sit at evenly spaced quantiles of the mix's clipped lognormal
(prompts rounded up to a power of two, so each length is one compiled
prefill program), arrival gaps at evenly spaced quantiles of the
exponential, as many as the window holds at the mix's rate.  The seed
shuffles their order and draws the prompt ids.  So two seeds do the
same work in another order.

Mix keys (``source`` and ``assumed`` say where the numbers come from;
the generator does not read them):
  loop                "open" (arrivals on a clock) or "closed" (one
                      client per slot; its next request is due when its
                      last one completes)
  prompt_tokens       {"median", "sigma", "min", "max"} lognormal, clipped
  output_tokens       the same for new tokens per request
  requests            closed loop: size of the pool, dealt in rounds of
                      one request per client
  rate_per_s          open loop: mean arrivals per second (the window of
                      ``seconds`` gets ceil(rate x seconds) arrivals)
  in_flight_at_open   open loop: requests admitted before the window
                      opens, so it opens near the steady state
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import statistics
from typing import List

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Planned:
    """One request of the schedule.  ``due_s`` is its offset from the
    window's open (open loop; negative: due before the window); the
    closed loop sets it when the client's previous request completes."""

    uid: int
    prompt: np.ndarray
    new_tokens: int
    client: int = -1
    due_s: float = 0.0


def load_mix(name: str) -> dict:
    with open(os.path.join(BENCH, "traffic", f"{name}.json")) as f:
        return json.load(f)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, pow2: bool) -> np.ndarray:
    """n lengths at evenly spaced quantiles of the clipped lognormal."""
    z = np.array([statistics.NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    x = np.clip(np.ceil(x), spec["min"], spec["max"]).astype(np.int64)
    if pow2:
        x = 2 ** np.ceil(np.log2(x)).astype(np.int64)
        x = np.minimum(x, spec["max"])
    return x


def prompt_buckets(mix: dict) -> List[int]:
    """Every prompt length the mix can send."""
    spec = mix["prompt_tokens"]
    lo = 2 ** math.ceil(math.log2(spec["min"]))
    out = []
    while lo <= spec["max"]:
        out.append(lo)
        lo *= 2
    return out


def _sizes(mix: dict, n: int, rng):
    """n (prompt, output) length pairs: one quantile set of each,
    shuffled by the seed."""
    return (rng.permutation(lengths(mix["prompt_tokens"], n, pow2=True)),
            rng.permutation(lengths(mix["output_tokens"], n, pow2=False)))


def schedule(mix: dict, seed: int, vocab: int, clients: int,
             seconds: float) -> List[Planned]:
    """The seeded schedule.  Closed loop: the pool in rounds of one
    request per client, each round one quantile set, so the requests
    due at set-up are the same sizes for every seed.  Open loop:
    ``in_flight_at_open`` requests due at the open (one quantile set,
    admitted during set-up), then ceil(rate x ``seconds``) arrivals at
    the shuffled gaps (another quantile set)."""
    rng = np.random.default_rng(int(seed))
    if mix["loop"] == "closed":
        groups = [clients] * (int(mix["requests"]) // clients)
    else:
        pre = int(mix["in_flight_at_open"])
        groups = [pre, math.ceil(float(mix["rate_per_s"]) * seconds)]
    n = sum(groups)
    plen, olen = (np.concatenate(x) for x in
                  zip(*(_sizes(mix, g, rng) for g in groups)))
    reqs = [Planned(uid=i,
                    prompt=rng.integers(0, vocab, size=int(plen[i]),
                                        dtype=np.int32),
                    new_tokens=int(olen[i])) for i in range(len(plen))]
    if mix["loop"] == "closed":
        for r in reqs:
            r.client = r.uid % clients
        return reqs
    gaps = rng.permutation(-np.log1p(-_quantiles(n - pre))
                           / float(mix["rate_per_s"]))
    for r in reqs[:pre]:
        r.due_s = -1.0
    for r, t in zip(reqs[pre:], np.cumsum(gaps)):
        r.due_s = float(t)
    return reqs
