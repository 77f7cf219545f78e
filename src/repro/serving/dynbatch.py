"""SpecPipe-DB: continuous-batching multi-request PipeDec engine.

The single-request engine (``core.pipedec``) gives the lowest latency but
leaves the pipeline idle whenever one task stalls; the paper's DB mode
keeps several requests' speculative token trees in flight at once — their
tree layers share every pipeline timestep (stacked along the batch axis in
each stage) and finished requests are replaced from the queue without
draining the pipeline (§ dynamic batching; 1.64–2.08× vLLM throughput in
the paper's Table).

Executor seam: the engine is the logical scheduler only — per-timestep
batched compute (fused tree-verify, batched commit, prune remap, admission
prefill) runs through a pluggable ``serving.executor.PipelineExecutor``.
``LocalFusedExecutor`` (default) is PR-2's fused single-device path: ONE
batched ``tree_verify`` per model per timestep over the slot-stacked
``KVArena``, power-of-two slot-count bucketing, batched exit commit.
``ShardedPipelineExecutor`` runs the same dispatches on the paper's
pipelined deployment — the target stack partitioned over an
``n_stages``-device mesh with the per-row metadata riding the ``ppermute``
activation ring (``launch.pipeline``), flushing each entry through all
stages so logits stay available at entry.  ``OverlappedShardedExecutor``
is the steady-state schedule on the same deployment: the ring persists
and stays full, the engine issues exactly ONE ring tick per executed
global timestep, each ``Flight`` carries a *deferred* logits future the
tick resolves at ``exit_t``, and misses/retirements kill the slot's
in-flight layers in-ring (pruning propagation).
``AsyncPipelineExecutor`` drops the host lockstep behind the same seam:
free-running per-stage actor threads pull ring layers from bounded inbox
queues and apply the very same per-stage step functions
(``launch.pipeline.make_stage_fns``), a disaggregated draft actor
speculates on its own device, and kill messages cancel stale in-flight
layers at whatever stage they sit.  Outputs are
bit-identical across all backends (and to the single-request engine)
because only *where and when* the verify logits materialise changes,
never *what* is computed — the same argument the paper makes for
losslessness; tests/test_serving_db.py and tests/test_executor_sharded.py
pin it.  Wall-clock is priced in ``core.sim.specpipe_db_*`` /
``specpipe_db_sharded_*`` (the overlapped schedule is the ``flush=False``
curve, measured).

Per-request *decisions* (flight bookkeeping, token selection with
per-request ``SamplingParams``, tree prune, index remaps) run through the
same ``PipeDecEngine`` phase methods (gather-entry / apply-fused /
exit-commit) the single-request engine uses — that engine is literally
the B=1 case of this code — so each request's operation trace is
identical to running it alone.  Tree expansion runs for every slot at
once, in one compiled program over the slot-stacked ``TreeBatch``
(``expand_rows``), which vmaps the per-tree function the single-request
``maybe_expand`` runs.  Between entry and exit the ``TreeBatch`` holds
the only copy of each slot's tree; a slot's tree is taken out only to
apply its exit.

Scheduling per global timestep:
  1. refill — admit arrived requests (priority/aging order, FIFO when
     priorities tie) onto free KV slots, running their prefill
     (join-on-prefill) through the executor into their arena rows.  On
     the overlapped backend the prefill rides the ring instead
     (``executor.begin_prefill``): the prompt enters the next tick's
     prefill lane — zero extra dispatches, the ring never idles — and
     the request parks as *joining* until the lane exits
     ``n_stages - 1`` ticks later, when its ``DecodeState`` is seeded
     from the resolved ``DeferredPrefill`` logits;
  2. advance — gather every active request's entry, run the fused verify,
     then the batched expansion and the (batched-commit) exit per slot;
  3. retire — requests that hit eos or their token budget release their
     slot (retire-on-eos) for the next refill.

Streaming: ``run(on_token=...)`` emits ``(uid, token, timestep)`` the
timestep each token is committed (the admission timestep for the prefill
token) instead of only at retire; the streamed prefix always equals the
final ``Result.tokens``.

Tracing: every executed timestep runs inside a ``specpipe.timestep``
profiler span (``jax.profiler.TraceAnnotation``; metadata ``step``,
``active``) whose children name its phases — ``specpipe.join``,
``specpipe.admit``, ``specpipe.entry``, ``specpipe.expand``,
``specpipe.exit``, ``specpipe.stream``, ``specpipe.retire`` — with one
``specpipe.admit.slot`` / ``expand.slot`` / ``exit.slot`` child per
request (metadata ``uid``, ``slot``); the executors add
``specpipe.executor.<method>`` spans around their calls.  With no
profiler running a span costs about a microsecond (0.8-1.3 us on a CPU
host), some 0.1 ms of a timestep.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.dynbatch import TreeBatch
from repro.core.pipedec import (DecodeState, EntryInputs, GenStats,
                                PipeDecConfig, PipeDecEngine)
from repro.core.speculative import ModelBundle
from repro.serving.executor import LocalFusedExecutor, PipelineExecutor
from repro.serving.scheduler import DynamicBatchScheduler, KVArena


@dataclasses.dataclass
class _Active:
    req: object
    state: DecodeState
    t0: float
    emitted: int = 0          # tokens already streamed via on_token


@dataclasses.dataclass
class _Joining:
    """A request whose admission prefill is riding the ring (overlapped
    backend with prefill-in-ring): the slot is allocated and the padded
    prompt advances one stage per tick inside the normal tick dispatch;
    once the ``DeferredPrefill`` future resolves (``n_stages - 1`` ticks
    after entry) the request's ``DecodeState`` is seeded from the
    resolved logits and it joins ``active``."""
    req: object
    key: jax.Array
    handle: object            # DeferredPrefill
    t0: float


@dataclasses.dataclass
class DBStats:
    """Aggregate engine statistics for one ``run()``.

    ``timesteps`` counts *executed* shared pipeline timesteps (idle gaps
    between sparse arrivals are fast-forwarded, not counted), so
    ``tokens_per_timestep`` prices what the pipeline does while busy and
    aligns 1:1 with the ``occupancy`` trace.  ``verify_dispatches`` traces
    the number of fused tree-verify calls per model per timestep (0 when
    no slot had a pending entry, otherwise exactly 1 — the fusion the
    equivalence test asserts via the executor's ``calls`` hook).
    ``tick_dispatches`` traces the overlapped backend's ring ticks per
    executed timestep — exactly 1 every timestep (the ring must advance
    even when no entry is pending); empty on the flush/local backends.
    ``accepted`` / ``proposed`` count speculative verify decisions per
    uid (a hit accepts the drafted node, a miss falls back to the target
    token), folded in at retire; their totals give the run's aggregate
    ``acceptance_rate`` — the regression currency of the int8 serving
    path.  ``hits`` / ``misses`` count the same decisions live, as each
    exit decides, and ``tokens_committed`` every committed token (each
    request's first, picked from its prefill, included) as it commits:
    they read mid-run, before any request retires.  ``expanded`` counts
    slots whose tree grew a layer and ``expand_deferred`` slots that held
    draft candidates but met the depth or capacity cap, live as well.
    ``separate_prefill_dispatches`` counts admissions that ran a
    standalone ``executor.prefill`` dispatch instead of riding the ring's
    (chunked) prefill lane — exactly 0 on the overlapped backend at ANY
    prompt length unless the lane is disabled.  ``page_counters`` traces
    the paged arena's pool counters per executed timestep (blocks in
    use/total/peak, fragmentation %, swaps, preemptions, copy-on-expand
    events); empty on dense arenas.
    """
    timesteps: int = 0
    total_commits: int = 0
    hits: int = 0
    misses: int = 0
    tokens_committed: int = 0
    expanded: int = 0
    expand_deferred: int = 0
    per_request: Dict[int, GenStats] = dataclasses.field(default_factory=dict)
    occupancy: List[int] = dataclasses.field(default_factory=list)
    verify_dispatches: List[int] = dataclasses.field(default_factory=list)
    tick_dispatches: List[int] = dataclasses.field(default_factory=list)
    accepted: Dict[int, int] = dataclasses.field(default_factory=dict)
    proposed: Dict[int, int] = dataclasses.field(default_factory=dict)
    total_accepted: int = 0
    total_proposed: int = 0
    separate_prefill_dispatches: int = 0
    page_counters: List[Dict] = dataclasses.field(default_factory=list)

    @property
    def tokens_per_timestep(self) -> float:
        return self.total_commits / self.timesteps if self.timesteps else 0.0

    @property
    def peak_occupancy(self) -> int:
        return max(self.occupancy) if self.occupancy else 0

    @property
    def acceptance_rate(self) -> float:
        """Aggregate accepted/proposed over every retired request."""
        return (self.total_accepted / self.total_proposed
                if self.total_proposed else 0.0)

    def acceptance_of(self, uid: int) -> float:
        prop = self.proposed.get(uid, 0)
        return self.accepted.get(uid, 0) / prop if prop else 0.0

    def record_acceptance(self, uid: int, st: GenStats) -> None:
        """Fold one request's verify decisions into the per-uid and
        aggregate counters (called at retire)."""
        self.accepted[uid] = st.hits
        self.proposed[uid] = st.hits + st.misses
        self.total_accepted += st.hits
        self.total_proposed += st.hits + st.misses


class SpecPipeDBEngine:
    """Dynamic-batching PipeDec: submit ``Request``s, then ``run()``."""

    def __init__(self, target: ModelBundle, draft: ModelBundle,
                 pcfg: Optional[PipeDecConfig] = None, *,
                 max_len: int = 512, max_slots: int = 4,
                 eos_token: Optional[int] = None, fused: bool = True,
                 executor: Optional[PipelineExecutor] = None):
        """``executor`` selects the compute backend (default:
        ``LocalFusedExecutor``); ``fused=False`` falls back to the looped
        per-slot dispatch (two ``tree_verify`` calls per request per
        timestep) — kept as the reference the fused-vs-looped equivalence
        test pins outputs against (local backend only)."""
        self.fused = fused
        self.pcfg = pcfg or PipeDecConfig()
        self.inner = PipeDecEngine(target, draft, self.pcfg, max_len=max_len)
        if executor is None:
            executor = LocalFusedExecutor(
                target, draft, slots=max_slots, max_len=max_len,
                tree_capacity=self.inner.tree_buffer_capacity,
                capacity=self.pcfg.capacity)
        assert executor.slots == max_slots, \
            "executor slot count must match max_slots"
        self.executor = executor
        self.arena = executor.arena
        assert fused or isinstance(self.arena, KVArena), \
            "looped (fused=False) mode needs the local KVArena backend"
        self.overlapped = bool(getattr(executor, "overlapped", False))
        if self.overlapped:
            assert fused, "the overlapped schedule is fused by construction"
            assert executor.n_stages == self.pcfg.n_stages, \
                ("overlapped executor: the mesh stage count must equal "
                 "PipeDecConfig.n_stages — the ring IS the flight "
                 "bookkeeping, so the fill latencies must agree")
        self.sched = DynamicBatchScheduler(self.arena)
        self.trees = TreeBatch(max_slots, self.pcfg.capacity)
        self.max_slots = max_slots
        self.eos_token = eos_token
        self.stats = DBStats()

    def submit(self, req) -> None:
        """Queue a request (``arrival_t`` is in global pipeline timesteps;
        requests join once arrived AND a KV slot is free, highest
        effective priority first)."""
        self.sched.submit(req)

    # ------------------------------------------------------------------
    def _timestep_guard(self) -> int:
        # prefill-in-ring adds an n_stages pipeline-fill delay between a
        # request's admission and its first entry — budget it per request,
        # plus one tick per extra prompt chunk when the prompt streams
        # through the lane over several ticks (chunked prefill)
        cap = getattr(self.executor, "prefill_cap", 0)
        chunks = lambda r: (
            max(-(-int(np.asarray(r.prompt).size) // cap), 1) - 1
            if cap else 0)
        per_req = sum(
            r.max_new_tokens * (self.pcfg.n_stages + 2) + 17
            + self.pcfg.n_stages + 1 + chunks(r)
            for r in self.sched.queue)
        arrivals = max((getattr(r, "arrival_t", 0)
                        for r in self.sched.queue), default=0)
        return 64 + arrivals + per_req

    # -- fused phase 1: stacked entry rows shared by all fused backends --
    def _entry_rows(self, active: Dict[int, _Active], pending: List[int]):
        """Stack every pending slot's entry layer (via the TreeBatch's
        vmapped deepest-layer view — no per-slot gather) into full-slot
        arrays.  Returns (tokens, positions, masks, model_len, write_idx,
        row_on, node_idx_b); non-pending rows are masked and only ever
        write into their own slack region."""
        p, tcap = self.pcfg, self.inner.tree_buffer_capacity
        nb = self.max_slots
        w = p.width

        row_on = np.zeros((nb,), bool)
        for slot in pending:
            row_on[slot] = True
        on = jnp.asarray(row_on)

        # stacked entry views of ALL slot rows (stale/non-pending rows are
        # masked below and only ever write into their own slack region)
        toks_b, idx_b, valid_b, mask_b = self.trees.deepest_layers(w)
        valid_b = valid_b & on[:, None]
        depth_b = jnp.take_along_axis(self.trees.stacked.depth, idx_b,
                                      axis=1)

        mlen_rows = np.zeros((nb,), np.int32)
        for slot in pending:
            mlen_rows[slot] = active[slot].state.model_len
        mlen = jnp.asarray(mlen_rows)

        # padded rows of a pending layer sit at model_len (depth 0), exactly
        # like the single-request gather; fully-masked slots sit at 0
        depths = jnp.where(valid_b, depth_b, 0)
        positions = jnp.where(on[:, None], mlen[:, None] + depths,
                              0).astype(jnp.int32)
        masks = jnp.pad(mask_b, ((0, 0), (0, 0),
                                 (0, tcap - mask_b.shape[-1])))
        masks = masks & valid_b[:, :, None]
        tokens = jnp.where(valid_b, toks_b, 0)
        # masked rows park their (never-attended) writes in the slack
        # region [capacity, capacity + w) of their OWN slot's tree buffer
        wi = jnp.where(on, self.trees.stacked.layer_start,
                       p.capacity).astype(jnp.int32)
        mlen = jnp.where(on, mlen, 0)

        # one host sync for every slot's node indices (the only entry
        # metadata the bookkeeping needs)
        node_idx_b = np.where(np.asarray(valid_b), np.asarray(idx_b),
                              -1).astype(np.int32)
        return tokens, positions, masks, mlen, wi, row_on, node_idx_b

    def _apply_entries(self, active: Dict[int, _Active],
                       pending: List[int], rows, v_of, d_all) -> None:
        """Scatter one dispatch's results back through ``apply_entry``:
        ``v_of(slot)`` supplies the slot's target verify logits — a row
        of the fused logits (flush/local) or a ``DeferredLogits`` future
        (overlapped)."""
        tokens, positions, masks, _, wi, _, node_idx_b = rows
        for slot in pending:
            entry = EntryInputs(tokens=tokens[slot],
                                positions=positions[slot],
                                mask=masks[slot], write_index=wi[slot],
                                node_idx=node_idx_b[slot])
            self.inner.apply_entry(active[slot].state, entry,
                                   v_of(slot), d_all[slot])

    def _fused_entry(self, active: Dict[int, _Active], stepping: List[int],
                     pending: List[int]):
        """Hand the executor ONE bucketed verify per model over the
        stacked entry rows, launch the expansion behind it, and scatter
        the logits back through ``apply_entry``.  Returns the launched
        expansion."""
        rows = self._entry_rows(active, pending)
        tokens, positions, masks, mlen, wi, row_on, node_idx_b = rows
        v_all, d_all = self.executor.verify_rows(tokens, positions, masks,
                                                 mlen, wi, row_on)
        launched = self._launch_expand(active, stepping, d_all, row_on,
                                       node_idx_b)
        self._apply_entries(active, pending, rows,
                            lambda slot: v_all[slot], d_all)
        return launched

    # -- shared per-timestep phases ------------------------------------
    def _bump(self, active: Dict[int, _Active],
              stepping: List[int]) -> List[int]:
        for slot in stepping:
            st = active[slot].state
            st.t += 1
            st.stats.timesteps = st.t
        return [s for s in stepping if active[s].state.pending]

    def _pick_exits(self, active: Dict[int, _Active],
                    stepping: List[int]) -> Dict[int, tuple]:
        picks = {}
        for slot in stepping:
            ev = self.inner.exit_pick(active[slot].state)
            if ev is not None:
                picks[slot] = ev
        return picks

    def _commit_exits(self, active: Dict[int, _Active], picks) -> None:
        """ONE batched two-level cache sync over every exiting slot."""
        if not picks:
            return
        mask_rows = np.zeros((self.max_slots,), bool)
        mlen_rows = np.zeros((self.max_slots,), np.int32)
        for slot in picks:
            mask_rows[slot] = True
            mlen_rows[slot] = active[slot].state.model_len
        self.executor.commit_rows(jnp.asarray(mlen_rows),
                                  jnp.asarray(mask_rows))

    def _apply_exits(self, active: Dict[int, _Active], stepping: List[int],
                     picks, *, kill_stale: bool = False) -> None:
        """Per-slot exit bookkeeping (token select, prune, flight remap),
        then ONE batched tree prune/remap over every pruned slot
        (``executor.remap_rows``; identity rows for the rest).  With
        ``kill_stale`` (overlapped backend) a miss additionally kills the
        slot's in-flight ring layers — the pruning-propagation stage."""
        remaps: Dict[int, np.ndarray] = {}
        for slot in stepping:
            if slot not in picks:
                continue
            a = active[slot]
            st = a.state
            with TraceAnnotation("specpipe.exit.slot", uid=a.req.uid,
                                 slot=slot):
                fl, root_row = picks[slot]
                misses0 = st.stats.misses
                st.tree = self.trees.get_row(slot)
                self.stats.tokens_committed += self.inner.exit_apply(
                    st, fl, root_row,
                    commit_caches=lambda _st: None,  # batched above
                    remap_caches=lambda _st, imap, s=slot:
                        remaps.__setitem__(s, imap))
                self.trees.set_row(slot, st.tree)
                st.tree = None
                missed = st.stats.misses > misses0
                self.stats.misses += missed
                self.stats.hits += not missed
                if kill_stale and missed:
                    self.executor.kill(slot)
        if remaps:
            imaps = np.tile(np.arange(self.pcfg.capacity, dtype=np.int32),
                            (self.max_slots, 1))
            row_mask = np.zeros((self.max_slots,), bool)
            for slot, imap in remaps.items():
                imaps[slot] = np.asarray(imap, np.int32)
                row_mask[slot] = True
            self.executor.remap_rows(imaps, row_mask)

    # ------------------------------------------------------------------
    def _advance_fused(self, active: Dict[int, _Active],
                       stepping: List[int]) -> None:
        """One shared pipeline timestep over all stepping slots: gather
        entries → ONE fused verify per model → ONE batched expansion →
        batched commit → batched prune/remap."""
        # phase 1: stacked gather-entry, ONE fused verify per model (the
        # pending flag alone decides participation — the entry inputs come
        # from the stacked TreeBatch views, not a per-slot gather)
        with TraceAnnotation("specpipe.entry"):
            pending = self._bump(active, stepping)
            if pending:
                launched = self._fused_entry(active, stepping, pending)
            else:
                launched = self._launch_expand(
                    active, stepping, None,
                    np.zeros((self.max_slots,), bool), None)
            self.stats.verify_dispatches.append(1 if pending else 0)

        self._expand(active, stepping, launched)

        # phase 2: exit — batched commit, then batched prune/remap
        with TraceAnnotation("specpipe.exit"):
            picks = self._pick_exits(active, stepping)
            self._commit_exits(active, picks)
            self._apply_exits(active, stepping, picks)

    def _launch_expand(self, active: Dict[int, _Active],
                       stepping: List[int], d_all, row_on, node_idx_b):
        """Enqueue ONE compiled expansion over every slot's tree
        (``TreeBatch.expand_rows``) right behind this timestep's verify,
        so it runs on the device while the host applies the entries.
        Due are the slots that entered now (``row_on``, with their entry
        ``node_idx_b``) and those still holding candidates from an
        earlier entry (deferred at the caps).  ``d_all``: the draft
        verify logits (None when nothing entered; the async backend's
        future is waited on here, its first consumer).  Returns (due
        [slots], grown [slots] on the device, or None)."""
        p = self.pcfg
        due = np.zeros((self.max_slots,), bool)
        nidx = np.full((self.max_slots, p.width), -1, np.int32)
        for slot in stepping:
            st = active[slot].state
            if row_on[slot]:
                idx = node_idx_b[slot]
            elif self.inner.wants_expand(st):
                idx = st.last_draft[0]
            else:
                continue
            due[slot] = bool((idx >= 0).any())
            nidx[slot] = idx
        if hasattr(d_all, "resolve"):
            d_all = d_all.resolve()
        if d_all is None and not due.any():
            return due, None
        return due, self.trees.expand_rows(
            d_all, row_on, nidx, due, w=p.width, c=p.branch,
            depth_cap=p.depth_cap)

    def _expand(self, active: Dict[int, _Active], stepping: List[int],
                launched) -> None:
        """The launched expansion's bookkeeping: one host read of which
        slots grew (each may defer at the caps), then per slot."""
        with TraceAnnotation("specpipe.expand"):
            due, grown = launched
            if grown is not None:
                grown = np.asarray(grown)
            for slot in stepping:
                a = active[slot]
                with TraceAnnotation("specpipe.expand.slot", uid=a.req.uid,
                                     slot=slot):
                    if due[slot]:
                        self.inner.record_expansion(a.state,
                                                    bool(grown[slot]))
                        self.stats.expanded += bool(grown[slot])
                        self.stats.expand_deferred += not grown[slot]

    # ------------------------------------------------------------------
    def _advance_overlapped(self, active: Dict[int, _Active],
                            stepping: List[int]) -> None:
        """One steady-state timestep: ONE ring tick interleaves the entry
        for timestep t with the exit for timestep t - (n_stages - 1).

        The tick always dispatches (the in-flight layers must advance a
        stage whether or not anything enters); entering slots receive
        ``DeferredLogits`` futures that this same tick resolves for the
        layers exiting NOW, so ``exit_apply`` consumes logits delivered
        at exit time.  Misses/retires kill the slot's in-flight layers
        in-ring; commits and prune maps are queued as the next tick's
        ctrl message, trailing the in-flight layers stage by stage."""
        with TraceAnnotation("specpipe.entry"):
            pending = self._bump(active, stepping)
            if pending:
                rows = self._entry_rows(active, pending)
            else:
                rows = (*self.executor.dead_entry,
                        np.zeros((self.max_slots,), bool), None)
            tokens, positions, masks, mlen, wi, row_on, node_idx_b = rows

            # phase 1: ONE ring tick — entry for t in, exit for
            # t - (n_stages - 1) out
            d_all, handles = self.executor.tick_rows(
                tokens, positions, masks, mlen, wi, row_on)
            self.stats.verify_dispatches.append(1 if pending else 0)
            self.stats.tick_dispatches.append(1)
            launched = self._launch_expand(active, stepping, d_all, row_on,
                                           node_idx_b)
            self._apply_entries(active, pending, rows,
                                lambda slot: handles[slot], d_all)

        self._expand(active, stepping, launched)

        # phase 2: exit — this tick's resolved futures; cache sync rides
        # the NEXT tick's ctrl (draft applies immediately)
        with TraceAnnotation("specpipe.exit"):
            picks = self._pick_exits(active, stepping)
            self._commit_exits(active, picks)
            self._apply_exits(active, stepping, picks, kill_stale=True)

    # ------------------------------------------------------------------
    def _stream(self, active: Dict[int, _Active], now: int,
                on_token: Optional[Callable]) -> None:
        """Emit every not-yet-streamed committed token as
        ``on_token(uid, token, timestep)`` (bounded by the request's
        token budget, mirroring ``DecodeState.output``)."""
        if on_token is None:
            return
        with TraceAnnotation("specpipe.stream"):
            for a in active.values():
                limit = 1 + a.state.max_new_tokens
                fresh = a.state.committed[a.emitted:limit]
                for tok in fresh:
                    on_token(a.req.uid, int(tok), now)
                a.emitted += len(fresh)

    # ------------------------------------------------------------------
    def run(self, key: Optional[jax.Array] = None,
            on_token: Optional[Callable] = None):
        """Drive the shared pipeline schedule until queue and slots drain.
        Returns {uid: Result} (same shape as ``ServingEngine.run``).
        ``on_token(uid, token, timestep)`` streams tokens at commit time."""
        base_key = key if key is not None else jax.random.PRNGKey(0)
        self.stats = DBStats()  # per-run aggregates (scheduler stats persist)
        results: Dict = {}
        active: Dict[int, _Active] = {}
        joining: Dict[int, _Joining] = {}
        ring_prefill = self.overlapped and \
            getattr(self.executor, "prefill_cap", 0) > 0
        guard = self._timestep_guard()
        now = 0

        while self.sched.pending or active or joining:
            if not active and not joining:
                # pipeline drained; fast-forward to the next arrival
                nxt = self.sched.next_arrival()
                if nxt is not None and nxt > now:
                    now = nxt
            with TraceAnnotation("specpipe.timestep",
                                 step=self.stats.timesteps + 1,
                                 active=len(active)):
                now = self._timestep(now, active, joining, results,
                                     base_key, ring_prefill, on_token)
            if now > guard:
                raise RuntimeError(
                    f"SpecPipeDBEngine exceeded timestep guard ({guard}); "
                    f"{len(active)} active, {self.sched.pending} queued")
        if self.overlapped:
            # every live flight resolved during the run (retires killed the
            # rest), so this is a no-op safety valve that leaves the
            # executor's ring clean for the next run
            self.executor.drain()
        return results

    def _timestep(self, now: int, active: Dict[int, _Active],
                  joining: Dict[int, _Joining], results: Dict,
                  base_key: jax.Array, ring_prefill: bool,
                  on_token: Optional[Callable]) -> int:
        """One executed global timestep (join, refill, advance, retire);
        returns the new timestep count ``now``."""
        from repro.serving.engine import Result

        # 0. join: requests whose in-ring admission prefill resolved
        # (its last tick exited the prompt's final hidden state) seed
        # their DecodeState from the resolved logits and go active —
        # the same init_state path, with the prefill already done
        if joining:
            with TraceAnnotation("specpipe.join"):
                for slot in [s for s in sorted(joining)
                             if joining[s].handle.ready]:
                    j = joining.pop(slot)
                    st = self.inner.init_state(
                        j.req.prompt, j.req.max_new_tokens, key=j.key,
                        eos=self.eos_token,
                        sampling=getattr(j.req, "sampling", None),
                        prefill_fn=lambda _p, h=j.handle: h.resolve())
                    self.stats.tokens_committed += 1
                    self.trees.adopt_row(slot, st.tree)
                    st.tree = None
                    active[slot] = _Active(j.req, st, j.t0)

        # 1. refill: join-on-prefill for arrived requests.  On the
        # overlapped backend the prefill enters the ring inside the
        # NEXT tick dispatch (prefill-in-ring: no separate dispatch,
        # no idle timestep) and the request parks in ``joining``
        # until its prompt exits the pipeline; other backends (and
        # prompts longer than the ring's prefill lane) prefill
        # through the executor immediately
        with TraceAnnotation("specpipe.admit"):
            for req, slot in self.sched.admit(now):
                with TraceAnnotation("specpipe.admit.slot", uid=req.uid,
                                     slot=slot):
                    self._admit_one(req, slot, active, joining, base_key,
                                    ring_prefill)
        self._stream(active, now, on_token)   # prefill (first) tokens

        # 2. advance: every active request shares this timestep
        now += 1
        self.stats.timesteps += 1
        stepping = [s for s in sorted(active) if not active[s].state.done]
        if self.overlapped:
            self._advance_overlapped(active, stepping)
        elif self.fused:
            self._advance_fused(active, stepping)
        else:
            for slot in stepping:
                st = active[slot].state
                before = dataclasses.replace(st.stats)
                st.tree = self.trees.get_row(slot)
                self.inner.step(st)
                self.trees.set_row(slot, st.tree)
                st.tree = None
                self.stats.hits += st.stats.hits - before.hits
                self.stats.misses += st.stats.misses - before.misses
                self.stats.tokens_committed += \
                    st.stats.commits - before.commits
                self.stats.expanded += st.stats.expanded - before.expanded
                self.stats.expand_deferred += \
                    st.stats.expand_deferred - before.expand_deferred
        self._stream(active, now, on_token)   # this timestep's commits

        # 3. retire: free slots for the next refill (fused mode: the
        # slot's caches already live in the executor's arena)
        with TraceAnnotation("specpipe.retire"):
            for slot in [s for s, a in active.items() if a.state.done]:
                a = active.pop(slot)
                st = a.state
                results[a.req.uid] = Result(
                    a.req.uid, st.output(),
                    time.perf_counter() - a.t0, st.stats)
                self.stats.per_request[a.req.uid] = st.stats
                self.stats.total_commits += st.stats.commits
                self.stats.record_acceptance(a.req.uid, st.stats)
                self.trees.release_row(slot)
                if self.overlapped:
                    # kill the retired request's in-flight ring layers and
                    # cancel its queued ctrl — the slot is being recycled
                    self.executor.kill(slot, drop_ctrl=True)
                self.sched.retire(
                    a.req.uid, slot, now,
                    caches=None if self.fused else st.caches())

        occ = len(active)
        self.stats.occupancy.append(occ)
        self.sched.stats.occupancy.append(occ)
        pages = getattr(self.arena, "pages", None)
        if pages is not None:
            self.stats.page_counters.append(pages.counters())
        return now

    def _admit_one(self, req, slot: int, active: Dict[int, _Active],
                   joining: Dict[int, _Joining], base_key: jax.Array,
                   ring_prefill: bool) -> None:
        """Admit one request onto ``slot``: its prefill enters the ring
        (it parks in ``joining``) or runs through the executor now."""
        rkey = jax.random.fold_in(base_key, req.uid)
        sampling = getattr(req, "sampling", None)
        if ring_prefill:
            h = self.executor.begin_prefill(slot, req.prompt)
            if h is not None:
                joining[slot] = _Joining(req, rkey, h, time.perf_counter())
                return
        if self.fused:
            self.stats.separate_prefill_dispatches += 1
            st = self.inner.init_state(
                req.prompt, req.max_new_tokens, key=rkey,
                eos=self.eos_token, sampling=sampling,
                prefill_fn=functools.partial(self.executor.prefill, slot))
        else:
            st = self.inner.init_state(
                req.prompt, req.max_new_tokens, key=rkey,
                caches=self.arena.caches(slot), eos=self.eos_token,
                sampling=sampling)
        self.stats.tokens_committed += 1
        self.trees.adopt_row(slot, st.tree)
        st.tree = None  # canonical copy lives in the TreeBatch
        active[slot] = _Active(req, st, time.perf_counter())

def generate_with_executor(target: ModelBundle, draft: ModelBundle,
                           pcfg: PipeDecConfig, prompt, max_new_tokens: int,
                           *, executor: Optional[PipelineExecutor] = None,
                           max_len: int = 512,
                           eos: Optional[int] = None,
                           key: Optional[jax.Array] = None,
                           sampling=None):
    """The B=1 PipeDec path on a pluggable compute backend: one request
    through a single-slot ``SpecPipeDBEngine`` (the single-request engine
    is literally the B=1 case of the DB schedule, so the output token
    sequence bit-matches ``PipeDecEngine.generate`` under greedy
    decoding).  Returns (tokens, GenStats)."""
    from repro.serving.engine import Request

    eng = SpecPipeDBEngine(target, draft, pcfg, max_len=max_len,
                           max_slots=1, eos_token=eos, executor=executor)
    eng.submit(Request(0, np.asarray(prompt), max_new_tokens,
                       sampling=sampling))
    res = eng.run(key=key)[0]
    return res.tokens, res.stats
