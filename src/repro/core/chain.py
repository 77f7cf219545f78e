"""Chain-mode speculative decoding for recurrent architectures (SSM /
RG-LRU hybrids) — DESIGN.md §Arch-applicability.

Attention-free models have no ancestor-mask trick: verifying a *tree* would
need one forked recurrent state per node.  The paper's pipeline-filling
idea still applies with tree width 1: the draft proposes a linear chain,
each pipeline stage processes a different chain position (PipeDec with
w = c = 1), and the recurrent state is checkpointed per chain position so a
mismatch rolls back to the accepted prefix.  Losslessness is identical:
every committed token is the target's own argmax/sample.

Logical engine (single device, exact information schedule): target states
are snapshotted functionally per speculative position; logits exit
``n_stages`` timesteps after entry.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.pipedec import GenStats
from repro.core.speculative import ModelBundle, SamplingParams, select_token


@dataclasses.dataclass
class ChainConfig:
    """Chain (width-1 tree) speculative pipeline config — the PipeInfer-
    style ablation of the dynamic tree.
    """
    n_stages: int = 4
    max_chain: int = 0  # 0 => n_stages + 4
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)

    @property
    def chain_cap(self) -> int:
        return self.max_chain or self.n_stages + 4


@dataclasses.dataclass
class _Flight:
    exit_t: int
    pos: int              # speculative chain position this logits verifies
    logits: jnp.ndarray   # [V]


class ChainSpecEngine:
    """Draft-in-pipeline chain speculative decoding for recurrent models."""

    def __init__(self, target: ModelBundle, draft: ModelBundle,
                 ccfg: ChainConfig, max_len: int = 512):
        assert target.cfg.vocab_size == draft.cfg.vocab_size
        self.target, self.draft, self.ccfg = target, draft, ccfg
        self.max_len = max_len

    def generate(self, prompt: np.ndarray, max_new_tokens: int,
                 key: Optional[jax.Array] = None):
        c = self.ccfg
        key = key if key is not None else jax.random.PRNGKey(0)
        tgt, drf = self.target, self.draft

        t_cache = tgt.init_cache(1, self.max_len)
        d_cache = drf.init_cache(1, self.max_len)
        prompt_j = jnp.asarray(prompt, jnp.int32)[None]
        t_logits, t_cache = tgt.prefill(prompt_j, t_cache)
        _, d_cache = drf.prefill(prompt_j, d_cache)
        model_len = len(prompt)

        key, sk = jax.random.split(key)
        committed = [int(select_token(t_logits[0], c.sampling, sk))]

        # speculative chain state: chain[0] = last committed token;
        # *_states[i] = cache/state AFTER processing chain[:i] tokens beyond
        # the committed prefix (so *_states[0] never contains speculation).
        chain: List[int] = [committed[-1]]
        t_states = [t_cache]
        d_states = [d_cache]
        spec_len = 0            # chain tokens processed so far
        flights: List[_Flight] = []
        stats = GenStats()
        t = 0
        limit = max_new_tokens * (c.n_stages + 2) + 16

        while len(committed) < 1 + max_new_tokens and t < limit:
            t += 1
            stats.timesteps = t

            # ---- entry: next unprocessed chain token enters the pipeline
            if spec_len < len(chain) and len(chain) <= c.chain_cap:
                tok = jnp.asarray([chain[spec_len]], jnp.int32)
                lg, new_cache = tgt.decode(tok, t_states[spec_len],
                                           model_len + spec_len)
                flights.append(_Flight(t + c.n_stages - 1, spec_len + 1,
                                       lg[0]))
                t_states.append(new_cache)

                # draft processes the same token and proposes the next one
                dlg, d_new = drf.decode(tok, d_states[spec_len],
                                        model_len + spec_len)
                d_states.append(d_new)
                chain.append(int(jnp.argmax(dlg[0])))
                spec_len += 1
                stats.entries += 1

            # ---- exit + sync -----------------------------------------
            exiting = [f for f in flights if f.exit_t == t]
            flights = [f for f in flights if f.exit_t != t]
            for fl in exiting:
                key, sk = jax.random.split(key)
                x = int(select_token(fl.logits, c.sampling, sk))
                committed.append(x)
                stats.commits += 1
                model_len += 1
                if fl.pos < len(chain) and chain[fl.pos] == x:
                    stats.hits += 1
                    # the chain prefix is consumed: shift the window
                    chain = chain[1:]
                    t_states = t_states[1:]
                    d_states = d_states[1:]
                    spec_len -= 1
                    for f2 in flights:
                        f2.pos -= 1
                else:
                    stats.misses += 1
                    # rollback to the state after the accepted prefix
                    # (chain[:pos] are committed tokens, so states are exact)
                    p = min(fl.pos, len(t_states) - 1)
                    chain = [x]
                    t_states = [t_states[p]]
                    d_states = [d_states[min(p, len(d_states) - 1)]]
                    spec_len = 0
                    flights = []
                if len(committed) >= 1 + max_new_tokens:
                    break

        return np.asarray(committed[: 1 + max_new_tokens]), stats
