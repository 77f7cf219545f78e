"""PipeDec decode engine — draft-in-pipeline speculative decoding.

This is the *logical* engine: it executes the exact computation and
information schedule of the paper's distributed system on one device.  The
pipeline-stage partition of the target model changes only *when* a layer's
logits become available (``n_stages - 1`` timesteps after the entry
timestep: the layer occupies stage 1 during the timestep it enters, so an
entry at timestep t exits at ``t + n_stages - 1`` and entry-to-exit spans
``n_stages`` timesteps inclusive — tests/test_serving_db.py pins this
pipeline-fill latency), never *what* is computed, so the single-device
engine is bit-identical to the multi-node system.  Wall-clock behaviour is
modelled separately (``core/sim.py``) and the sharded deployment lives in
``repro.launch``.

Per timestep (paper §3.4, Fig. 2):
  1. the current deepest tree layer *enters* the pipeline: the target
     computes its verification logits (buffered until exit) and the draft
     processes the same layer to propose the next layer (tree expand);
  2. the layer that entered ``n_stages`` timesteps ago *exits*: the logits
     row of the current root gives the next committed token x; the root's
     KV row migrates from the tree cache to the model cache (two-level
     cache sync, §3.4.3); the tree is pruned to the subtree of the child
     matching x (hit) or re-initialised at x (miss), and all in-flight
     state is remapped/invalidated accordingly.

The per-request loop state lives in ``DecodeState`` and one timestep is
``PipeDecEngine.step``; ``generate`` drives a single state to completion,
while the dynamic-batching engine (``repro.serving.dynbatch``) multiplexes
many states through one shared pipeline schedule — each request's operation
trace is identical either way, so SpecPipe-DB inherits losslessness from
this engine.

Vanilla pipeline parallelism is the degenerate case w=0 (every step a
miss); STPP (static tree) is in ``core/baselines.py``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tree as tree_lib
from repro.core.speculative import (ModelBundle, SamplingParams,
                                    draft_candidates, named_jit,
                                    remap_tree_caches, select_token)

_draft_candidates_jit = named_jit("draft_candidates", draft_candidates,
                                  static_argnames=("c",))
_expand_tree_jit = named_jit("expand_tree", tree_lib.expand_from_draft,
                             static_argnames=("w", "depth_cap"))


@dataclasses.dataclass
class PipeDecConfig:
    """Dynamic-tree SpecPipe config: stage count, max tree layer width
    w, max children per node c, tree depth cap and sampling.
    """
    n_stages: int = 4
    width: int = 8            # max tree layer width w
    branch: int = 4           # max children per node c
    max_depth: int = 0        # 0 => n_stages + 4
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)

    @property
    def depth_cap(self) -> int:
        return self.max_depth or self.n_stages + 4

    @property
    def capacity(self) -> int:
        return 1 + self.width * self.depth_cap

    @property
    def tree_buffer_capacity(self) -> int:
        """Tree KV buffer rows: ``capacity`` plus width-w slack so every
        fixed-width layer write (and masked DB rows parked at
        ``capacity``) fits without clamping."""
        return self.capacity + self.width


@dataclasses.dataclass
class Flight:
    """One in-flight tree layer between entry and exit.

    ``logits`` is either the concrete [w, V] verify logits (the flush /
    local schedules compute them at entry and buffer them here) or a
    *deferred* handle exposing ``resolve() -> [w, V]`` (the overlapped
    sharded schedule — the layer is still riding the stage ring and its
    logits only exist at ``exit_t``, when the backend resolves the
    future).  ``exit_apply`` resolves at consumption time, so the engine
    schedule is identical either way."""
    exit_t: int
    node_idx: np.ndarray      # [w] int32 global tree indices (-1 invalid)
    logits: Any               # [w, V] array, or a deferred-logits handle


@dataclasses.dataclass
class EntryInputs:
    """One request's deepest tree layer, ready for the (fused) tree-verify
    dispatch — the per-slot unit the DB engine stacks along the batch axis
    (``TreeBatch.deepest_layers`` produces the same views already stacked).
    """
    tokens: jnp.ndarray       # [w] int32 layer tokens (padded with 0)
    positions: jnp.ndarray    # [w] int32 absolute positions
    mask: jnp.ndarray         # [w, Tcap] padded ancestor-mask rows
    write_index: jnp.ndarray  # () int32 tree-buffer write offset
    node_idx: np.ndarray      # [w] int32 global tree indices (-1 invalid)


def remap_flight_indices(node_idx: np.ndarray, index_map) -> np.ndarray:
    """Apply a prune's old→new ``index_map`` to buffered flight/draft node
    indices (-1 rows stay -1; dropped nodes become -1).  int32 in, int32
    out — all tree/flight indices share one dtype across hit/prune cycles
    (tests pin the stability)."""
    imap = np.asarray(index_map)
    out = np.where(node_idx >= 0, imap[np.maximum(node_idx, 0)], -1)
    return out.astype(np.int32)


@dataclasses.dataclass
class GenStats:
    """Per-request SpecPipe counters: timesteps, commits, hit/miss
    verifications, ring entries, and tree layers grown or deferred at
    the depth/capacity caps.
    """
    timesteps: int = 0
    commits: int = 0
    hits: int = 0
    misses: int = 0
    entries: int = 0
    expanded: int = 0
    expand_deferred: int = 0

    @property
    def acceptance(self) -> float:
        tot = self.hits + self.misses
        return self.hits / tot if tot else 0.0

    @property
    def tokens_per_timestep(self) -> float:
        return self.commits / self.timesteps if self.timesteps else 0.0


@dataclasses.dataclass
class DecodeState:
    """Everything one in-flight request carries between timesteps."""
    committed: List[int]
    tree: tree_lib.Tree
    t_cache: Any              # target model (level-1) KV cache
    d_cache: Any              # draft model cache
    t_tree: Any               # target tree (level-2) KV cache
    d_tree: Any               # draft tree cache
    model_len: int
    key: jax.Array
    max_new_tokens: int
    limit: int                # local-timestep budget
    flights: List[Flight] = dataclasses.field(default_factory=list)
    pending: bool = True      # deepest layer not yet entered
    last_draft: Optional[Tuple[np.ndarray, jnp.ndarray]] = None
    stats: GenStats = dataclasses.field(default_factory=GenStats)
    t: int = 0                # local timestep counter
    eos: Optional[int] = None
    eos_hit: bool = False
    sampling: Optional[SamplingParams] = None  # per-request (None => cfg's)

    @property
    def done(self) -> bool:
        return (self.eos_hit
                or len(self.committed) >= 1 + self.max_new_tokens
                or self.t >= self.limit)

    def output(self) -> np.ndarray:
        return np.asarray(self.committed[: 1 + self.max_new_tokens])

    def caches(self):
        return (self.t_cache, self.d_cache, self.t_tree, self.d_tree)


class PipeDecEngine:
    """Single-request SpecPipe engine: drives the dynamic token tree
    through the stage ring one timestep at a time (entry at t exits
    at t + n_stages - 1) and commits on the hit path.
    """
    def __init__(self, target: ModelBundle, draft: ModelBundle,
                 pcfg: PipeDecConfig, max_len: int = 512):
        assert target.cfg.vocab_size == draft.cfg.vocab_size
        self.target, self.draft, self.pcfg = target, draft, pcfg
        self.max_len = max_len

    # ------------------------------------------------------------------
    def _pad_mask(self, mask_rows: jnp.ndarray, tcap: int) -> jnp.ndarray:
        n, cap = mask_rows.shape
        return jnp.pad(mask_rows, ((0, 0), (0, tcap - cap)))

    @property
    def tree_buffer_capacity(self) -> int:
        return self.pcfg.tree_buffer_capacity

    # ------------------------------------------------------------------
    def init_state(self, prompt: np.ndarray, max_new_tokens: int,
                   key: Optional[jax.Array] = None,
                   max_timesteps: Optional[int] = None, *,
                   caches=None, eos: Optional[int] = None,
                   sampling: Optional[SamplingParams] = None,
                   prefill_fn=None) -> DecodeState:
        """Prefill both models and commit the first token.

        ``caches`` optionally supplies recycled (t_cache, d_cache, t_tree,
        d_tree) buffers (the serving KV arena): prefill overwrites the
        prompt prefix and every attention mask is bounded by ``model_len``
        / the ancestor mask, so stale rows from a previous occupant are
        never attended and outputs are unchanged.

        ``prefill_fn`` hands the prefill to an executor backend that owns
        the cache storage (``serving.executor.PipelineExecutor.prefill``):
        it receives the [1, len] prompt, fills both models' caches
        wherever the backend keeps them, and returns the target's
        last-position logits; the state then carries no cache pytrees of
        its own (they live in the executor's arena).

        ``sampling`` overrides the engine-global ``pcfg.sampling`` for
        this request only (per-request temperature/top-k/top-p — mixed
        greedy/stochastic batches under SpecPipe-DB).
        """
        p = self.pcfg
        key = key if key is not None else jax.random.PRNGKey(0)
        tcap = self.tree_buffer_capacity
        sp = sampling if sampling is not None else p.sampling

        tgt, drf = self.target, self.draft
        prompt_j = jnp.asarray(prompt, jnp.int32)[None]
        if prefill_fn is not None:
            t_cache = d_cache = t_tree = d_tree = None
            t_logits = prefill_fn(prompt_j)
        else:
            if caches is None:
                t_cache = tgt.init_cache(1, self.max_len)
                d_cache = drf.init_cache(1, self.max_len)
                t_tree = tgt.init_tree_caches(1, tcap)
                d_tree = drf.init_tree_caches(1, tcap)
            else:
                t_cache, d_cache, t_tree, d_tree = caches
            t_logits, t_cache = tgt.prefill(prompt_j, t_cache)
            _, d_cache = drf.prefill(prompt_j, d_cache)

        prefix = 0
        if tgt.prefix_embeds is not None:
            prefix = tgt.prefix_embeds.shape[1]
        model_len = prefix + len(prompt)

        key, sk = jax.random.split(key)
        first = int(select_token(t_logits[0], sp, sk))

        st = DecodeState(
            committed=[first],
            tree=tree_lib.tree_init(p.capacity, first),
            t_cache=t_cache, d_cache=d_cache, t_tree=t_tree, d_tree=d_tree,
            model_len=model_len, key=key, max_new_tokens=max_new_tokens,
            limit=max_timesteps or (max_new_tokens * (p.n_stages + 2) + 16),
            eos=eos, sampling=sp)
        st.eos_hit = eos is not None and first == eos
        return st

    # ---- phase 1a: gather-entry (pure read) --------------------------
    def gather_entry(self, st: DecodeState) -> Optional["EntryInputs"]:
        """Read the deepest tree layer as stacked-axis-ready entry inputs.
        No state change; returns None when no layer is pending entry.  The
        DB engine stacks these across slots for ONE fused tree-verify
        dispatch per model; ``step`` runs the same arrays at B=1."""
        if not st.pending:
            return None
        w = self.pcfg.width
        tokens, idxs, valid, mask_rows = tree_lib.last_layer(st.tree, w)
        depths = jnp.where(valid, st.tree.depth[idxs], 0)
        positions = (st.model_len + depths).astype(jnp.int32)       # [w]
        pmask = self._pad_mask(mask_rows, self.tree_buffer_capacity)
        node_idx = np.where(np.asarray(valid), np.asarray(idxs),
                            -1).astype(np.int32)
        return EntryInputs(tokens=tokens, positions=positions, mask=pmask,
                           write_index=st.tree.layer_start,
                           node_idx=node_idx)

    # ---- phase 1b: apply-fused (bookkeeping from the verify logits) --
    def apply_entry(self, st: DecodeState, entry: "EntryInputs",
                    v_logits, d_logits: jnp.ndarray) -> None:
        """Record the entry's in-flight state from this request's rows of
        the (possibly fused) tree-verify logits ([w, V] each).

        ``v_logits`` may be a deferred handle instead of an array (the
        overlapped sharded backend delivers the target's verify logits at
        exit time; see ``Flight``).  ``d_logits`` is always concrete —
        the draft proposes the next layer the same timestep, so it runs
        beside stage 0 with no pipeline delay on every backend."""
        st.flights.append(Flight(exit_t=st.t + self.pcfg.n_stages - 1,
                                 node_idx=entry.node_idx,
                                 logits=v_logits))
        st.stats.entries += 1
        st.last_draft = (entry.node_idx.copy(), d_logits)
        st.pending = False

    # ---- phase 1c: tree expansion (may be deferred) ------------------
    @staticmethod
    def wants_expand(st: DecodeState) -> bool:
        """The request holds draft candidates for a layer still in the
        tree: an expansion is due (it may be deferred at the caps)."""
        return (st.last_draft is not None and not st.pending
                and bool((st.last_draft[0] >= 0).any()))

    @staticmethod
    def record_expansion(st: DecodeState, grown: bool) -> None:
        """Bookkeeping of one due expansion: a grown layer is pending
        entry; a deferred one keeps its candidates and is retried next
        timestep, once a prune frees room."""
        if grown:
            st.pending = True
            st.last_draft = None
            st.stats.expanded += 1
        else:
            st.stats.expand_deferred += 1

    def maybe_expand(self, st: DecodeState) -> None:
        """Grow the request's tree by the layer its draft proposed at the
        last entry.  The batched engine runs the same
        ``tree_lib.expand_from_draft`` over every slot at once
        (``core.dynbatch.expand_rows``)."""
        if not self.wants_expand(st):
            return
        p = self.pcfg
        nidx, dlog = st.last_draft
        tok, lp = _draft_candidates_jit(dlog, jnp.ones(nidx.shape, bool),
                                        c=p.branch)
        st.tree, grown = _expand_tree_jit(st.tree, tok, lp, nidx, True,
                                          w=p.width, depth_cap=p.depth_cap)
        self.record_expansion(st, bool(grown))

    # ---- phase 2a: pick the exiting flight ---------------------------
    def exit_pick(self, st: DecodeState) -> Optional[Tuple[Flight, int]]:
        """Pop the flight exiting this timestep.  Returns (flight,
        root_row) or None (nothing exiting, or a stale flight whose root
        was pruned away — should not happen)."""
        exiting = [f for f in st.flights if f.exit_t == st.t]
        st.flights = [f for f in st.flights if f.exit_t != st.t]
        for fl in exiting:
            root_rows = np.where(fl.node_idx == 0)[0]
            if len(root_rows):
                return fl, int(root_rows[0])
        return None

    # ---- phase 2b: exit-commit (token, prune, remap) -----------------
    def exit_apply(self, st: DecodeState, fl: Flight, root_row: int, *,
                   commit_caches, remap_caches) -> int:
        """Commit the root's token and sync all in-flight state.  Cache
        mutation is delegated: ``commit_caches(st)`` migrates tree-buffer
        row 0 into the model caches at ``st.model_len`` (two-level cache
        sync, §3.4.3) and ``remap_caches(st, index_map)`` compacts the
        tree caches after a prune — the single-request engine mutates
        ``st``'s own caches, the DB engine its arena rows.  Returns the
        number of commits (1)."""
        p = self.pcfg
        sp = st.sampling if st.sampling is not None else p.sampling
        st.key, sk = jax.random.split(st.key)
        logits = fl.logits
        if hasattr(logits, "resolve"):   # deferred future: resolved by the
            logits = logits.resolve()    # backend the tick the layer exits
        x = int(select_token(logits[root_row], sp, sk))
        st.committed.append(x)
        st.stats.commits += 1
        commit_caches(st)
        st.model_len += 1
        if st.eos is not None and x == st.eos:
            st.eos_hit = True

        hit = int(tree_lib.find_child_with_token(st.tree, x))
        if hit >= 0:
            st.stats.hits += 1
            st.tree, index_map = tree_lib.tree_prune_to_child(st.tree, hit)
            remap_caches(st, index_map)
            for f2 in st.flights:
                f2.node_idx = remap_flight_indices(f2.node_idx, index_map)
            if st.last_draft is not None:
                st.last_draft = (remap_flight_indices(st.last_draft[0],
                                                      index_map),
                                 st.last_draft[1])
        else:
            st.stats.misses += 1
            st.tree = tree_lib.tree_init(p.capacity, x)
            st.flights = []
            st.last_draft = None
            st.pending = True
        return 1

    # default cache plumbing: the request owns its caches (B=1)
    def _commit_own_caches(self, st: DecodeState) -> None:
        st.t_cache = self.target.commit(st.t_cache, st.t_tree, 0,
                                        st.model_len)
        st.d_cache = self.draft.commit(st.d_cache, st.d_tree, 0,
                                       st.model_len)

    def _remap_own_caches(self, st: DecodeState, index_map) -> None:
        cap = self.pcfg.capacity
        st.t_tree = remap_tree_caches(st.t_tree, index_map, cap)
        st.d_tree = remap_tree_caches(st.d_tree, index_map, cap)

    def step(self, st: DecodeState) -> DecodeState:
        """Advance one pipeline timestep: gather-entry → verify (target
        entry + draft proposal) → expansion → exit-commit.  Mutates and
        returns ``st``.  The DB engine drives the same phases with the
        verify dispatch fused across slots; this per-request path is its
        B=1 case."""
        st.t += 1
        st.stats.timesteps = st.t

        entry = self.gather_entry(st)
        if entry is not None:
            v_logits, st.t_tree = self.target.tree_verify(
                entry.tokens[None], entry.positions[None], entry.mask[None],
                st.t_cache, st.model_len, st.t_tree, entry.write_index)
            d_logits, st.d_tree = self.draft.tree_verify(
                entry.tokens[None], entry.positions[None], entry.mask[None],
                st.d_cache, st.model_len, st.d_tree, entry.write_index)
            self.apply_entry(st, entry, v_logits[0], d_logits[0])

        self.maybe_expand(st)

        ev = self.exit_pick(st)
        if ev is not None:
            fl, root_row = ev
            self.exit_apply(
                st, fl, root_row, commit_caches=self._commit_own_caches,
                remap_caches=self._remap_own_caches)
        return st

    # ------------------------------------------------------------------
    def generate(self, prompt: np.ndarray, max_new_tokens: int,
                 key: Optional[jax.Array] = None,
                 max_timesteps: Optional[int] = None, *,
                 eos: Optional[int] = None,
                 sampling: Optional[SamplingParams] = None):
        st = self.init_state(prompt, max_new_tokens, key, max_timesteps,
                             eos=eos, sampling=sampling)
        while not st.done:
            self.step(st)
        return st.output(), st.stats
