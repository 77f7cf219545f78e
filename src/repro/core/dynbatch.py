"""Batched prediction-tree state for dynamic batching (SpecPipe-DB).

The multi-request engine (``repro.serving.dynbatch``) keeps every in-flight
request's dynamic prediction tree stacked along a leading *slot* axis, the
paper's DB state layout: one fixed-capacity ``Tree`` buffer per KV slot,
all stored as a single pytree of ``[slots, ...]`` arrays.  Per-request
operations (init on admission, prune-to-child on commit) are the pure
``core.tree`` functions applied to one row and written back, and expansion
vmaps the per-tree ``tree_lib.expand_from_draft`` over every row, so a DB
request's tree trace is bit-identical to the single-request engine's —
the property the equivalence tests pin.

``deepest_layers`` exposes the stacked view of every slot's entry layer
(tokens / indices / validity / ancestor-mask rows, all ``[slots, w, ...]``)
via ``jax.vmap`` — the fusion point: the DB engine feeds it (with per-row
``model_len`` / ``tree_write_index`` / masks) into ONE batched
``tree_verify`` dispatch per model per timestep
(``ModelBundle.tree_verify_rows``).  ``expand_rows`` is the other half:
ONE compiled program per timestep grows every slot's tree from the
draft's verify logits (``tree_lib.expand_from_draft`` under ``vmap``).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tree as tree_lib
from repro.core.speculative import draft_candidates, named_jit


def expand_rows(stacked: tree_lib.Tree, cands, d_all, row_on, node_idx,
                expand_on, *, w: int, c: int, depth_cap: int):
    """Every slot's expansion at once.

    ``cands`` ([slots, w, c] tokens and logprobs) holds each slot's draft
    candidates from its last entry; rows that entered now (``row_on``)
    take fresh ones from ``d_all`` ([nb, w, V], the draft verify logits
    of slot rows ``[0, nb)``; None when nothing entered).  Candidates
    are per row, so they are taken in entry order and carried until the
    slot grows, however long the caps defer it.  Slots with
    ``expand_on`` grow from their entry rows' current ``node_idx``
    [slots, w].  Returns (stacked tree, candidates, grown [slots])."""
    cand_tok, cand_lp = cands
    if d_all is not None:
        nb = d_all.shape[0]
        tok, lp = draft_candidates(d_all, jnp.ones(d_all.shape[:2], bool), c)
        fresh = row_on[:nb, None, None]
        cand_tok = cand_tok.at[:nb].set(jnp.where(fresh, tok, cand_tok[:nb]))
        cand_lp = cand_lp.at[:nb].set(jnp.where(fresh, lp, cand_lp[:nb]))
    grow = functools.partial(tree_lib.expand_from_draft, w=w,
                             depth_cap=depth_cap)
    stacked, grown = jax.vmap(grow)(stacked, cand_tok, cand_lp, node_idx,
                                    expand_on)
    return stacked, (cand_tok, cand_lp), grown


_expand_rows_jit = named_jit("expand_rows", expand_rows,
                             static_argnames=("w", "c", "depth_cap"))


class TreeBatch:
    """Fixed-slot store of prediction trees stacked along axis 0."""

    def __init__(self, slots: int, capacity: int):
        assert slots >= 1 and capacity >= 1
        self.slots, self.capacity = slots, capacity
        proto = tree_lib.tree_init(capacity, 0)
        self.stacked: tree_lib.Tree = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (slots, *x.shape)).copy(),
            proto)
        self.active = np.zeros((slots,), bool)
        self.cands = None      # [slots, w, c] draft candidates (expand_rows)

    # -- row access -----------------------------------------------------
    def _check(self, slot: int) -> None:
        assert 0 <= slot < self.slots, f"slot {slot} out of range"

    def get_row(self, slot: int) -> tree_lib.Tree:
        self._check(slot)
        return jax.tree.map(lambda x: x[slot], self.stacked)

    def set_row(self, slot: int, tree: tree_lib.Tree) -> None:
        self._check(slot)
        self.stacked = jax.tree.map(lambda b, r: b.at[slot].set(r),
                                    self.stacked, tree)

    # -- per-request tree ops (reuse core.tree on one row) --------------
    def init_row(self, slot: int, root_token: int) -> tree_lib.Tree:
        """Admission: fresh single-root tree in ``slot``."""
        t = tree_lib.tree_init(self.capacity, root_token)
        self.adopt_row(slot, t)
        return t

    def adopt_row(self, slot: int, tree: tree_lib.Tree) -> None:
        """Admission of an already-built tree (the decode state's)."""
        assert tree.capacity == self.capacity
        self.set_row(slot, tree)
        self.active[slot] = True

    def release_row(self, slot: int) -> None:
        """Retire: the slot may be recycled by the next admission."""
        self._check(slot)
        self.active[slot] = False

    def expand_row(self, slot: int, cand_tokens: jnp.ndarray,
                   cand_logprobs: jnp.ndarray, w: int) -> tree_lib.Tree:
        t = tree_lib.tree_expand(self.get_row(slot), cand_tokens,
                                 cand_logprobs, w)
        self.set_row(slot, t)
        return t

    def prune_row(self, slot: int,
                  child_idx) -> Tuple[tree_lib.Tree, jnp.ndarray]:
        """Prune one slot's tree to a depth-1 child; returns (tree,
        old→new index_map) so the caller can remap its in-flight state."""
        t, index_map = tree_lib.tree_prune_to_child(self.get_row(slot),
                                                    child_idx)
        self.set_row(slot, t)
        return t, index_map

    def expand_rows(self, d_all, row_on: np.ndarray, node_idx: np.ndarray,
                    expand_on: np.ndarray, *, w: int, c: int,
                    depth_cap: int) -> jnp.ndarray:
        """Grow every ``expand_on`` slot's tree in ONE program
        (``expand_rows``); returns which grew, on the device (read it
        once, when the host needs it)."""
        if self.cands is None:
            self.cands = (jnp.zeros((self.slots, w, c), jnp.int32),
                          jnp.full((self.slots, w, c), tree_lib.NEG_INF))
        self.stacked, self.cands, grown = _expand_rows_jit(
            self.stacked, self.cands, d_all, row_on, node_idx, expand_on,
            w=w, c=c, depth_cap=depth_cap)
        return grown

    # -- stacked views ---------------------------------------------------
    def deepest_layers(self, w: int):
        """Every slot's entry layer, stacked: (tokens [S,w], idx [S,w],
        valid [S,w], mask_rows [S,w,N]).  Inactive slots still produce rows
        (their stale trees); the fused dispatch masks them with
        ``self.active`` / its pending set so they only ever write into
        their own slot's slack region."""
        return jax.vmap(lambda tr: tree_lib.last_layer(tr, w))(self.stacked)

    def occupancy(self) -> int:
        return int(self.active.sum())
