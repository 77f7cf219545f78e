"""Drive ``SpecPipeDBEngine.run`` on a wall clock.

The engine counts arrivals in timesteps and drains its queue, so the
benchmark feeds it from outside, through its public ``submit``: a
wrapper around the scheduler's ``admit`` (called once at the start of
every executed timestep) delivers every request that is due by the wall
clock, opens the window once the requests due at set-up are admitted,
and ends the window by raising.  When the engine drains between
arrivals, the feed sleeps until the next one is due and calls ``run``
again.  Tokens are timed as the engine streams them (``on_token``).

Host spans (``jax.profiler.TraceAnnotation``) wrap each timestep and
each executor call, so a traced run can say what the host was doing
while the device sat idle.  Each call of a model bundle that launches a
compiled program (prefill, tree verify, commit) runs inside a launch
span of its own (``bench.launch.<role>_<method>``), so the trace
reduction can tell the target's programs from the draft's although the
program names them alike.

The logits the engine picks its tokens from are kept for the check
against the reference (``Rows``): the target's and the draft's prefill
logits of every admitted request, the target's verify row that commits
each later token, and the draft's verify row at each tree root it
enters.  They stay on the device until the window has closed.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Dict, List, Optional

import jax
import numpy as np

import flops as fl
from traffic import Planned

SPANS = ("verify_rows", "prefill", "commit_rows", "remap_rows")
# the model bundles' calls that each launch one compiled program
LAUNCHES = ("prefill", "tree_verify_rows", "commit_rows")


class WindowClosed(Exception):
    """Raised from inside ``run`` when the measured window ends."""


def make_engine_class():
    from repro.serving.dynbatch import SpecPipeDBEngine

    class BenchEngine(SpecPipeDBEngine):
        """The engine with its livelock guard sized from the whole seeded
        schedule of the run (requests arrive after ``run`` starts, so the
        queue at the start does not bound the run)."""

        schedule_budget = 0

        def _timestep_guard(self) -> int:
            return super()._timestep_guard() + self.schedule_budget

    return BenchEngine


def guard_budget(plan: List[Planned], n_stages: int, prefill_cap: int) -> int:
    """The engine's per-request timestep budget, summed over the plan."""
    chunks = (lambda n: max(-(-n // prefill_cap), 1) - 1) if prefill_cap \
        else (lambda n: 0)
    return sum(r.new_tokens * (n_stages + 2) + 17 + n_stages + 1
               + chunks(len(r.prompt)) for r in plan)


def build(conf: dict, target_w, draft_w):
    """(executor, engine) for a configuration's ``serving`` block."""
    from repro.core.pipedec import PipeDecConfig
    from repro.core.speculative import ModelBundle
    from repro.serving import LocalFusedExecutor

    sv = conf["serving"]
    pcfg = PipeDecConfig(n_stages=sv["n_stages"], width=sv["width"],
                         branch=sv["branch"])
    target, draft = (ModelBundle(w.params, w.family.program_config(w.shape,
                                                                    role))
                     for role, w in (("target", target_w),
                                     ("draft", draft_w)))
    kw = dict(slots=sv["slots"], max_len=sv["max_len"],
              tree_capacity=pcfg.tree_buffer_capacity,
              capacity=pcfg.capacity)
    executor = LocalFusedExecutor(target, draft, **kw)
    engine = make_engine_class()(target, draft, pcfg, max_len=sv["max_len"],
                                 max_slots=sv["slots"], executor=executor)
    return executor, engine


@dataclasses.dataclass
class CallLog:
    """What the window's program calls need, kept as device references
    and reduced after the window closes."""

    verify: list = dataclasses.field(default_factory=list)
    prefill: list = dataclasses.field(default_factory=list)


class Rows:
    """The logit rows the engine picked each served token from, by role
    and ``(uid, k)``: row k predicts the request's k-th served token
    (k = 0 is the prefill's last position).  The target has a row for
    every served token; the draft for the prefill and for each tree root
    it entered (after every miss).  Rows stay on the device until
    ``fetch``."""

    def __init__(self):
        self.kept = {"target": {}, "draft": {}}

    def keep(self, role: str, uid: int, k: int, row) -> None:
        self.kept[role][(uid, k)] = row

    def fetch(self, uids) -> dict:
        """``{role: {(uid, k): np.ndarray [V]}}`` for ``uids``, on the
        host; every other row is dropped."""
        uids = set(uids)
        out = {role: {key: np.asarray(row, np.float32).reshape(-1)
                      for key, row in rows.items() if key[0] in uids}
               for role, rows in self.kept.items()}
        self.kept = {"target": {}, "draft": {}}
        return out


class Feeder:
    """One run of a schedule through one engine (see the module doc)."""

    def __init__(self, engine, executor, plan: List[Planned], loop: str,
                 seconds: float, *, clock=time.perf_counter,
                 on_open=None, trace_s: Optional[float] = None,
                 on_trace_end=None, stagger: int = 1):
        self.engine, self.executor = engine, executor
        self.seconds, self.clock = seconds, clock
        self.on_open, self.on_trace_end = on_open, on_trace_end
        self.trace_s = trace_s
        self.spans = trace_s is not None
        self.mark = None         # host-clock readings count from here
        self.steps_since_mark = 0
        self.plan = {r.uid: r for r in plan}
        self.phase = "fill"
        self.t_open = self.t_close = None
        self.due: Dict[int, float] = {}
        self.delivered: Dict[int, float] = {}
        self.admitted: Dict[int, float] = {}
        self.token_t: Dict[int, list] = collections.defaultdict(list)
        self.token_id: Dict[int, list] = collections.defaultdict(list)
        self.completed: Dict[int, float] = {}
        self.timesteps = 0
        self.step_t: List[float] = []      # window timesteps' starts
        self.calls = CallLog()
        self.rows = Rows()
        self._slot_uid: Dict[int, int] = {}
        self._uid_of: Dict[int, int] = {}
        self._admitting = -1
        self._ready: List[Planned] = []      # due, not yet submitted
        self._future: List[Planned] = []     # open loop: in due order
        self._next_of: Dict[int, collections.deque] = {}
        self._step_span = None
        if loop == "closed":
            clients = collections.defaultdict(collections.deque)
            for r in plan:
                clients[r.client].append(r)
            self._next_of = clients
            self._fill = [q.popleft() for q in clients.values()]
        else:
            self._fill = [r for r in plan if r.due_s < 0]
            self._future = sorted((r for r in plan if r.due_s >= 0),
                                  key=lambda r: r.due_s)
        self._fill_uids = {r.uid for r in self._fill}
        # the requests due at set-up join in ``stagger`` groups on
        # consecutive timesteps, so their tree cycles (one commit every
        # n_stages timesteps at zero acceptance) do not run in lockstep
        self._fill_groups = [self._fill[i::stagger] for i in range(stagger)]
        self._wrap()

    # -- the engine's seams ---------------------------------------------
    def _span(self, name, fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            if not self.spans:
                return fn(*args, **kwargs)
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                return fn(*args, **kwargs)
        return call

    def _wrap(self):
        ex, eng = self.executor, self.engine
        for name in SPANS:
            setattr(ex, name, self._span(name, getattr(ex, name)))
        for role, bundle in (("target", eng.inner.target),
                             ("draft", eng.inner.draft)):
            for method in LAUNCHES:
                setattr(bundle, method, self._span(
                    f"launch.{role}_{method}", getattr(bundle, method)))
            bundle.tree_verify_rows = self._log_verify(
                role, bundle.tree_verify_rows)
            bundle.prefill = self._keep_prefill(role, bundle.prefill)
        ex.prefill = self._log_prefill(ex.prefill)
        inner = eng.inner
        inner.init_state = self._tag_state(inner.init_state)
        inner.apply_entry = self._keep_draft_root(inner.apply_entry)
        inner.exit_apply = self._keep_commit_row(inner.exit_apply)
        self._admit = eng.sched.admit
        eng.sched.admit = self._on_admit

    def _in_window(self) -> bool:
        return self.phase == "window"

    def _log_verify(self, role, fn):
        def call(node_tokens, node_positions, tree_mask, cache, cache_len,
                 tree_caches, tree_write_index, *, bucket):
            if self._in_window():
                self.calls.verify.append((self.clock(), role, tree_mask,
                                          cache_len))
            return fn(node_tokens, node_positions, tree_mask, cache,
                      cache_len, tree_caches, tree_write_index,
                      bucket=bucket)
        return call

    def _log_prefill(self, fn):
        def call(slot, prompt):
            self._admitting = self._slot_uid[slot]
            if self._in_window():
                self.calls.prefill.append((self.clock(), int(prompt.size)))
            return fn(slot, prompt)
        return call

    # -- the rows the tokens were picked from (see ``Rows``) -------------
    def _keep_prefill(self, role, fn):
        def call(tokens, cache):
            logits, cache = fn(tokens, cache)
            self.rows.keep(role, self._admitting, 0, logits)
            return logits, cache
        return call

    def _tag_state(self, fn):
        def call(*args, **kwargs):
            st = fn(*args, **kwargs)
            self._uid_of[id(st)] = self._admitting
            return st
        return call

    def _keep_draft_root(self, fn):
        def call(st, entry, v_logits, d_logits):
            fn(st, entry, v_logits, d_logits)
            root = np.flatnonzero(np.asarray(entry.node_idx) == 0)
            if root.size:
                self.rows.keep("draft", self._uid_of[id(st)],
                               len(st.committed), d_logits[int(root[0])])
        return call

    def _keep_commit_row(self, fn):
        def call(st, fl, root_row, **kwargs):
            k = len(st.committed)
            out = fn(st, fl, root_row, **kwargs)
            logits = fl.logits
            if hasattr(logits, "resolve"):
                logits = logits.resolve()
            self.rows.keep("target", self._uid_of[id(st)], k,
                           logits[root_row])
            return out
        return call

    def _submit(self, r: Planned, now_step: int) -> None:
        from repro.serving.engine import Request
        self.delivered[r.uid] = self.clock()
        self.engine.submit(Request(r.uid, r.prompt, r.new_tokens,
                                   arrival_t=now_step))

    def _on_admit(self, now_step: int):
        t = self.clock()
        if self._step_span is not None:
            self._step_span.__exit__(None, None, None)
            self._step_span = None
        if self.phase == "fill" and self._fill_groups and self.admitted:
            self._ready.extend(self._fill_groups.pop(0))
        if self.phase == "fill" and self._fill_uids <= self.admitted.keys():
            self.phase = "window"
            self.t_open = t
            for r in self._future:
                self.due[r.uid] = t + r.due_s
            if self.on_open is not None:
                self.on_open()
            t = self.clock()
            if not self.spans:
                self.mark = t
        if self.phase == "window":
            if self.spans and t >= self.t_open + self.trace_s:
                # the traced part is over: host-clock readings start now
                self.spans = False
                self.on_trace_end()
                t = self.mark = self.clock()
            if t >= self.t_open + self.seconds:
                self.t_close = t
                self.phase = "closed"
                raise WindowClosed
            self._deliver_due(t)
            self.timesteps += 1
            self.step_t.append(t)
            if self.mark is not None:
                self.steps_since_mark += 1
        for r in self._ready:
            self._submit(r, now_step)
        self._ready = []
        if self.spans:
            self._step_span = jax.profiler.TraceAnnotation("bench.timestep")
            self._step_span.__enter__()
        out = self._admit(now_step)
        t = self.clock()
        for req, slot in out:
            self.admitted[req.uid] = t
            self._slot_uid[slot] = req.uid
        return out

    def _deliver_due(self, t: float) -> None:
        while self._future and self.due[self._future[0].uid] <= t:
            self._ready.append(self._future.pop(0))

    def _on_token(self, uid, token, _step):
        t = self.clock()
        self.token_t[uid].append(t)
        self.token_id[uid].append(int(token))
        r = self.plan[uid]
        if len(self.token_id[uid]) == 1 + r.new_tokens:
            self.completed[uid] = t
            q = self._next_of.get(r.client)
            if q:
                nxt = q.popleft()
                self.due[nxt.uid] = t
                self._ready.append(nxt)

    # -- the run ---------------------------------------------------------
    def run(self, key) -> None:
        """Fill, open the window, run until it closes."""
        self._ready = self._fill_groups.pop(0)
        for r in self._fill:
            self.due[r.uid] = self.clock()
        try:
            while True:
                if not self._ready and not self.engine.sched.pending:
                    if self.phase == "fill":
                        raise RuntimeError("the engine drained before the "
                                           "requests due at set-up joined")
                    self._sleep_to_next()
                    if self.phase == "closed":
                        return
                self._submit_ready_direct()
                self.engine.run(key=key, on_token=self._on_token)
        except WindowClosed:
            return
        finally:
            if self._step_span is not None:
                self._step_span.__exit__(None, None, None)
                self._step_span = None

    def _submit_ready_direct(self) -> None:
        for r in self._ready:
            self._submit(r, 0)
        self._ready = []

    def _sleep_to_next(self) -> None:
        """The engine drained: wait for the next arrival or the close."""
        end = self.t_open + self.seconds
        nxt = self.due[self._future[0].uid] if self._future else end
        wait = min(nxt, end) - self.clock()
        if wait > 0:
            if self.spans:
                with jax.profiler.TraceAnnotation("bench.idle_wait"):
                    time.sleep(wait)
            else:
                time.sleep(wait)
        t = self.clock()
        if t >= end:
            self.t_close = t
            self.phase = "closed"
            return
        self._deliver_due(t)

    # -- readings ----------------------------------------------------------
    def window_tokens(self, start: Optional[float] = None) -> int:
        """Tokens streamed from ``start`` (the open) to the close."""
        lo = self.t_open if start is None else start
        return sum(lo <= t <= self.t_close for ts in self.token_t.values()
                   for t in ts)

    def token_gaps_ms(self, start: Optional[float] = None) -> List[float]:
        """Gaps between a request's consecutive tokens, both streamed
        from ``start`` (the open) to the close."""
        lo = self.t_open if start is None else start
        out = []
        for ts in self.token_t.values():
            for a, b in zip(ts, ts[1:]):
                if a >= lo and b <= self.t_close:
                    out.append((b - a) * 1e3)
        return out

    def longest_timestep(self):
        """(ms, s into the window) of the longest timestep in it."""
        ts = self.step_t + [self.t_close]
        if len(ts) < 2:
            return 0.0, 0.0
        ms, at = max(((b - a) * 1e3, a - self.t_open)
                     for a, b in zip(ts, ts[1:]))
        return ms, at

    def lateness_ms(self) -> List[float]:
        """How late the generator handed each request over."""
        return [(self.delivered[u] - d) * 1e3 for u, d in self.due.items()
                if u in self.delivered and u not in self._fill_uids]

    def queue_wait_ms(self) -> dict:
        """Due to admission of the requests due in the window (those not
        admitted by the close count their wait so far): p50 and max."""
        waits = [(min(self.admitted.get(u, self.t_close), self.t_close) - d)
                 * 1e3 for u, d in self.due.items()
                 if u not in self._fill_uids and d < self.t_close]
        return {"n": len(waits), "p50": percentile(waits, 50),
                "max": max(waits, default=None)}

    def waiting(self) -> int:
        """Requests due before the close and not admitted by it."""
        return sum(1 for u, d in self.due.items() if d < self.t_close
                   and self.admitted.get(u, float("inf")) > self.t_close)

    def attempted(self) -> int:
        return len(self._fill_uids) + sum(
            1 for u, d in self.due.items()
            if u not in self._fill_uids and d < self.t_close)


def percentile(values, q: float) -> Optional[float]:
    """The q-th percentile (linear between order statistics)."""
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), q))


def verify_work(calls: CallLog, target, draft, peak: dict, lo: float,
                hi: float):
    """(useful FLOPs, {role: least seconds}, {role: verify calls}) of the
    logged calls made between ``lo`` and ``hi``, counted by each model's
    family from its shape (``target``, ``draft``: ``model.Weights``).
    Useful work counts the valid tree nodes that the target verifies and
    the draft proposes from, and each prompt token prefilled."""
    useful = 0.0
    least = collections.defaultdict(float)
    n_calls = collections.Counter()
    for t, role, mask, ctx in calls.verify:
        if not lo <= t <= hi:
            continue
        mask, ctx = np.asarray(mask), np.asarray(ctx, np.float64)
        on = mask.any(-1)                       # [rows, width] valid nodes
        anc = mask.sum(-1)                      # tree rows each node sees
        nodes = int(on.sum())
        context = float((ctx[:, None] * on).sum() + anc.sum())
        kv_rows = float(ctx[on.any(-1)].sum() + anc.sum() + nodes)
        w = draft if role == "draft" else target
        f, b = w.family.verify_call(w.shape, w.shape.layers, nodes, context,
                                    kv_rows)
        useful += f
        least[role] += fl.least_time(f, b, peak)
        n_calls[role] += 1
    for t, n in calls.prefill:
        if lo <= t <= hi:
            useful += sum(w.family.prefill_flops(w.shape, n)
                          for w in (target, draft))
    return useful, dict(least), dict(n_calls)
