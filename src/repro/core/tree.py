"""Dynamic prediction tree (paper §3.3) — fixed-capacity functional form.

The paper stores the tree as flat GPU arrays in BFS order: token array X,
probability array P, child-count array C and an ancestor mask matrix M, and
mutates them in place.  JAX needs static shapes, so the tree lives in a
fixed-capacity buffer of ``capacity`` slots with a packed prefix of
``n_nodes`` valid entries (BFS order preserved), and all three operations —
init / expand / prune — are pure functions:

  * ``tree_init``    — single root node (the last committed token).
  * ``tree_expand``  — append one layer: draft candidates ``[w, c]`` are
    scored by cumulative log-probability ``B = M·log P`` (paper's formula,
    computed incrementally via per-node cumulative logprob), the global
    top-``min(w, ...)`` are appended (paper §3.3.3).  Always appends a
    *fixed* ``w`` slots; invalid ones carry -inf logprob and are excluded
    from the mask, so downstream attention never sees them.
  * ``tree_prune_to_child`` — keep the subtree rooted at a depth-1 child and
    *compact* it back to the buffer prefix (the paper keeps dead entries in
    place; compaction is our TPU adaptation so the buffer never overflows).
    Returns the old→new index map so in-flight pipeline state (buffered
    logits, KV-cache rows) can be remapped identically.

The ancestor mask ``M`` is maintained incrementally like the paper's
block-matrix update: a new node's row = parent's row + its own one-hot.
``M`` is ancestor-or-self (diagonal set), exactly what tree attention needs.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

NEG_INF = jnp.float32(-1e30)


class Tree(NamedTuple):
    """The dynamic prediction tree, packed into fixed-capacity arrays:
    per-node token/logprob/parent/depth, the ancestor-or-self
    attention mask and the packed prefix/deepest-layer bounds.
    """
    tokens: jnp.ndarray       # [N] int32
    logprob: jnp.ndarray      # [N] f32 cumulative log-prob from root (root=0)
    parent: jnp.ndarray       # [N] int32, -1 for root / invalid
    depth: jnp.ndarray        # [N] int32 (root=0), -1 invalid
    mask: jnp.ndarray         # [N, N] bool, ancestor-or-self
    n_nodes: jnp.ndarray      # () int32 packed prefix length
    layer_start: jnp.ndarray  # () int32 first index of the deepest layer
    layer_size: jnp.ndarray   # () int32 valid nodes in the deepest layer

    @property
    def capacity(self) -> int:
        return self.tokens.shape[0]

    def valid(self) -> jnp.ndarray:
        return jnp.arange(self.capacity) < self.n_nodes


def tree_init(capacity: int, root_token) -> Tree:
    """Fresh single-node tree holding ``root_token`` at index 0."""
    tokens = jnp.zeros((capacity,), jnp.int32).at[0].set(
        jnp.asarray(root_token, jnp.int32))
    logprob = jnp.full((capacity,), NEG_INF).at[0].set(0.0)
    parent = jnp.full((capacity,), -1, jnp.int32)
    depth = jnp.full((capacity,), -1, jnp.int32).at[0].set(0)
    mask = jnp.zeros((capacity, capacity), bool).at[0, 0].set(True)
    one = jnp.asarray(1, jnp.int32)
    return Tree(tokens, logprob, parent, depth, mask,
                n_nodes=one, layer_start=jnp.asarray(0, jnp.int32),
                layer_size=one)


def last_layer(tree: Tree, w: int) -> Tuple[jnp.ndarray, jnp.ndarray,
                                            jnp.ndarray, jnp.ndarray]:
    """Deepest layer padded to ``w``: (tokens [w], node_idx [w], valid [w],
    mask_rows [w, N] ancestor-or-self rows for those nodes)."""
    idx = tree.layer_start + jnp.arange(w)
    valid = jnp.arange(w) < tree.layer_size
    safe = jnp.where(valid, idx, 0)
    tokens = jnp.where(valid, tree.tokens[safe], 0)
    mask_rows = tree.mask[safe] & valid[:, None]
    return tokens, safe, valid, mask_rows


def tree_expand(tree: Tree, cand_tokens: jnp.ndarray,
                cand_logprobs: jnp.ndarray, w: int) -> Tree:
    """Append one layer from draft candidates of the current deepest layer.

    cand_tokens/cand_logprobs: [w, c] — row i corresponds to the i-th node of
    the deepest layer (padded rows must carry -inf logprob).  Appends exactly
    ``w`` buffer slots; ``layer_size`` counts the valid ones.
    """
    n = tree.capacity
    c = cand_tokens.shape[1]
    row_valid = jnp.arange(w) < tree.layer_size
    parent_idx = tree.layer_start + jnp.arange(w)
    parent_idx = jnp.where(row_valid, parent_idx, 0)

    # cumulative log-prob of each candidate = parent's cumulative + log q
    parent_lp = jnp.where(row_valid, tree.logprob[parent_idx], NEG_INF)
    cum = cand_logprobs + parent_lp[:, None]          # [w, c]
    cum = jnp.where(row_valid[:, None], cum, NEG_INF)

    flat = cum.reshape(-1)                            # [w*c]
    k = min(w, flat.shape[0])
    top_lp, top_ix = jax.lax.top_k(flat, k)
    # don't overflow the buffer
    space = n - tree.n_nodes
    slot_ok = (jnp.arange(k) < space) & (top_lp > NEG_INF / 2)
    new_size = slot_ok.sum().astype(jnp.int32)

    sel_parent = parent_idx[top_ix // c]
    sel_token = cand_tokens.reshape(-1)[top_ix]
    start = tree.n_nodes
    dest = start + jnp.arange(k, dtype=jnp.int32)
    dest_safe = jnp.where(slot_ok, dest, n)           # OOB -> dropped

    tokens = tree.tokens.at[dest_safe].set(sel_token, mode="drop")
    logprob = tree.logprob.at[dest_safe].set(top_lp, mode="drop")
    parent = tree.parent.at[dest_safe].set(sel_parent, mode="drop")
    depth = tree.depth.at[dest_safe].set(
        tree.depth[sel_parent] + 1, mode="drop")
    new_rows = tree.mask[sel_parent]                  # [k, N] parent rows
    new_rows = new_rows | jax.nn.one_hot(dest_safe, n, dtype=bool)
    mask = tree.mask.at[dest_safe].set(new_rows, mode="drop")

    return Tree(tokens, logprob, parent, depth, mask,
                n_nodes=start + new_size,
                layer_start=start, layer_size=new_size)


def can_grow(tree: Tree, w: int, depth_cap: int) -> jnp.ndarray:
    """Whether one more ``w``-wide layer may be appended: the deepest
    node lies above ``depth_cap`` and ``n_nodes + w`` fits the buffer
    NOW (``n_nodes + w == capacity + 1`` would make ``tree_expand``
    truncate the layer's last candidate at the buffer edge)."""
    cur_depth = jnp.max(jnp.where(tree.valid(), tree.depth, 0))
    return (cur_depth < depth_cap) & (tree.n_nodes + w <= tree.capacity)


def expand_from_draft(tree: Tree, cand_tokens: jnp.ndarray,
                      cand_logprobs: jnp.ndarray, node_idx: jnp.ndarray,
                      on, w: int, depth_cap: int) -> Tuple[Tree, jnp.ndarray]:
    """Grow the layer that entered with ``node_idx`` from its draft
    candidates, if ``on`` and the caps allow (``can_grow``).

    ``cand_tokens``/``cand_logprobs`` [w, c] are the top-c draft
    candidates of each entered row, in entry order, and ``node_idx`` [w]
    the rows' tree indices after any prune since (-1: row invalid or
    pruned away).  The surviving rows, sorted by index, align with the
    deepest layer's nodes.  Returns (tree, grown): the old tree where
    nothing grows.  The one definition of an expansion, run per request
    (``PipeDecEngine.maybe_expand``) and vmapped over the slot-stacked
    trees (``core.dynbatch.expand_rows``)."""
    valid = node_idx >= 0
    order = jnp.argsort(jnp.where(valid, node_idx,
                                  jnp.iinfo(jnp.int32).max))
    lp = jnp.where(valid[order][:, None], cand_logprobs[order], NEG_INF)
    grown = tree_expand(tree, cand_tokens[order], lp, w)
    ok = jnp.asarray(on) & can_grow(tree, w, depth_cap)
    return jax.tree.map(lambda new, old: jnp.where(ok, new, old),
                        grown, tree), ok


def find_child_with_token(tree: Tree, token, parent_idx=0) -> jnp.ndarray:
    """hit_index (paper §3.3.4): node index of the child of ``parent_idx``
    whose token equals ``token``; -1 on miss."""
    is_child = (tree.parent == parent_idx) & tree.valid()
    hit = is_child & (tree.tokens == jnp.asarray(token, jnp.int32))
    any_hit = hit.any()
    idx = jnp.argmax(hit)  # first (= highest-probability, BFS order) match
    return jnp.where(any_hit, idx, -1).astype(jnp.int32)


def root_argmax_child(tree: Tree) -> jnp.ndarray:
    """Most probable depth-1 child (for greedy draft-only flows)."""
    is_child = (tree.parent == 0) & (tree.depth == 1) & tree.valid()
    score = jnp.where(is_child, tree.logprob, NEG_INF)
    return jnp.argmax(score).astype(jnp.int32)


def tree_prune_to_child(tree: Tree, child_idx) -> Tuple[Tree, jnp.ndarray]:
    """Prune to the subtree rooted at ``child_idx`` (a depth-1 node) and
    compact (paper §3.3.4: keep = column ``M[:, hit]``).

    Returns (new_tree, index_map [N] int32) with index_map[i] = new index of
    old node i, or -1 if dropped.
    """
    n = tree.capacity
    keep = tree.mask[:, child_idx] & tree.valid()      # descendants-or-self
    index_map = jnp.where(keep, jnp.cumsum(keep) - 1, -1).astype(jnp.int32)
    new_n = keep.sum().astype(jnp.int32)

    # gather order: old indices of surviving nodes, BFS order preserved
    order_key = jnp.where(keep, jnp.arange(n), n + jnp.arange(n))
    g = jnp.argsort(order_key)                          # [N] old idx per new

    live = jnp.arange(n) < new_n
    tokens = jnp.where(live, tree.tokens[g], 0)
    logprob = jnp.where(live, tree.logprob[g] - tree.logprob[child_idx],
                        NEG_INF)
    depth = jnp.where(live, tree.depth[g] - 1, -1)
    old_parent = tree.parent[g]
    parent = jnp.where(live,
                       jnp.where(g == child_idx, -1,
                                 index_map[jnp.where(old_parent >= 0,
                                                     old_parent, 0)]),
                       -1).astype(jnp.int32)
    mask = tree.mask[g][:, g] & live[:, None] & live[None, :]
    # new root must not keep its old ancestors: the gather already dropped
    # them (they were not descendants of child_idx).

    new_layer_start = index_map[tree.layer_start]
    # the old deepest layer may have been partially pruned; count survivors
    old_layer = (jnp.arange(n) >= tree.layer_start) & \
        (jnp.arange(n) < tree.layer_start + tree.layer_size)
    surv = (old_layer & keep).sum().astype(jnp.int32)
    # if the whole old deepest layer died, the deepest layer is the last one
    # with any survivors; recompute from depth
    max_depth = jnp.max(jnp.where(live, depth, -1))
    is_deepest = live & (depth == max_depth)
    layer_start = jnp.argmax(is_deepest).astype(jnp.int32)
    layer_size = is_deepest.sum().astype(jnp.int32)

    return Tree(tokens, logprob, parent, depth, mask,
                n_nodes=new_n, layer_start=layer_start,
                layer_size=layer_size), index_map
