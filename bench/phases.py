"""Split a profiler trace by the program's own spans: where the engine's
timestep spends its host time, which phase launched each device program,
and what the host was doing in each idle gap of the device.

  python3 bench/phases.py TRACE_DIR [--save FILE.json.gz] [--steps N]

reads the ``.xplane.pb`` under TRACE_DIR (a traced run kept by
``run.run_cell(..., keep_trace=TRACE_DIR)``, e.g. through
``tests/record_trace.py``), prints the reduction as one JSON line and the
clock offset on standard error; ``--save`` keeps ``load``'s tuples
(the first N timesteps with ``--steps``) in the form ``tests/data`` keeps.

``load`` keeps three things apart from what ``trace.load`` reads:

- the program's host spans, every event named ``specpipe.*``
  (``serving/dynbatch.py``, ``serving/executor.py``), with their metadata
  (``step``, ``active``, ``uid``, ``slot``);
- for each device program run, the instant its host thread asked for
  it: the earliest host event carrying the run's ``run_id`` or, where
  that event runs on a runtime worker thread (``DoEnqueueProgram`` on
  ``pjrt-tpu-tasks``, 22% of the runs on a v5e), the start of the event
  on the calling thread that handed it over (the profiler's flow ids:
  ``_pt``/``_p`` on the producer, ``_ct``/``_c`` on the consumer around
  the worker's event);
- each chip's program runs (the "XLA Modules" line) with their ``run_id``.

``reduce`` works on those tuples only.  The device clock and the host
clock disagree by more than a short program lasts, so it estimates the
offset from the runs matched to their enqueue events (a run starts no
earlier than it was enqueued: the offset is the largest lead of a run's
start over its enqueue, ignoring the top ``LEAD_QUANTILE`` of leads),
moves the device's runs onto the host clock, and then

- attributes each run to the innermost program span around its enqueue,
  and to the engine phase (a direct child of ``specpipe.timestep``) that
  holds it;
- labels every stretch of idle device time by the innermost program span
  over it (cut at span edges, so the labels sum to the idle total); a
  request's own span (``expand.slot``, ``exit.slot``, ``admit.slot``)
  counts for its phase;
- gives each phase's host self time per timestep: its spans' time less
  the executor calls (``specpipe.executor.*``) inside them.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import gzip
import json
import re
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

PREFIX = "specpipe."
TIMESTEP = "timestep"
EXECUTOR = "executor."
OUTSIDE = "outside program spans"
DEVICE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE = "XLA Modules"
# the share of the largest leads of a run over its enqueue that the
# offset estimate ignores (a run matched to the wrong enqueue event)
LEAD_QUANTILE = 0.01

Span = Tuple[str, float, float, dict]     # name, start_ns, dur_ns, meta
Run = Tuple[str, float, float, int]       # program, start_ns, dur_ns, run_id


@dataclasses.dataclass
class Recording:
    """What ``load`` keeps: the program's spans (names without the
    ``specpipe.`` prefix) and each run's enqueue instant ``{run_id:
    start_ns}`` on the host clock, and each chip's program runs on its
    own clock."""

    spans: List[Span]
    enqueues: Dict[int, float]
    runs: Dict[str, List[Run]]


def load(path: str) -> Recording:
    from jax.profiler import ProfileData
    spans, runs = [], {}
    produced = {}              # flow (type, id) -> its producer's start
    asked = []                 # (run_id, start, flow of the event around)
    for plane in ProfileData.from_file(path).planes:
        if DEVICE.match(plane.name):
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    runs[plane.name] = [
                        (e.name, e.start_ns, e.duration_ns,
                         int(dict(e.stats).get("run_id", 0)))
                        for e in line.events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                around = (None, -1.0)      # the last consumer's flow, end
                for e in line.events:
                    if e.name.startswith("$"):      # the Python tracer's
                        continue
                    if e.name.startswith(PREFIX):
                        spans.append((e.name[len(PREFIX):], e.start_ns,
                                      e.duration_ns, dict(e.stats)))
                        continue
                    st = dict(e.stats)
                    if "_p" in st:
                        produced[(st["_pt"], st["_p"])] = e.start_ns
                    if "_c" in st:
                        around = ((st["_ct"], st["_c"]),
                                  e.start_ns + e.duration_ns)
                    if "run_id" in st:
                        asked.append((int(st["run_id"]), e.start_ns,
                                      around[0] if e.start_ns <= around[1]
                                      else None))
    enqueues = {}
    for rid, t, flow in asked:
        t = min(t, produced.get(flow, t))
        enqueues[rid] = min(t, enqueues.get(rid, t))
    return Recording(spans, enqueues, runs)


def trim(rec: Recording, steps: int) -> Recording:
    """The first ``steps`` whole timesteps of a recording (and the runs
    and enqueues of that stretch)."""
    ts = sorted(s for s in rec.spans if s[0] == TIMESTEP)[:steps]
    lo, hi = ts[0][1], ts[-1][1] + ts[-1][2]
    keep = lambda t: lo <= t <= hi  # noqa: E731
    slack = 50e6                    # a run may sit off by a clock offset
    return Recording(
        [s for s in rec.spans if keep(s[1]) and keep(s[1] + s[2])],
        {r: t for r, t in rec.enqueues.items() if keep(t)},
        {p: [r for r in rs if lo - slack <= r[1] <= hi + slack]
         for p, rs in rec.runs.items()})


def save(rec: Recording, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump({"spans": rec.spans,
                   "enqueues": sorted(rec.enqueues.items()),
                   "runs": rec.runs}, f)


def restore(path: str) -> Recording:
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    return Recording([tuple(s) for s in d["spans"]],
                     {int(r): t for r, t in d["enqueues"]},
                     {p: [tuple(r) for r in rs]
                      for p, rs in d["runs"].items()})


def label(name: str) -> str:
    """A span's label: a request's own span counts for its phase."""
    return name[:-len(".slot")] if name.endswith(".slot") else name


class Cover:
    """The spans over each instant: the span edges cut the time line
    into pieces, each with the chain of spans that cover it (outermost
    first; the spans of one thread nest)."""

    def __init__(self, spans: List[Span]):
        iv = sorted(((s, s + d, n) for n, s, d, _ in spans),
                    key=lambda x: (x[0], -x[1]))
        self.edges = sorted({x for s, e, _ in iv for x in (s, e)})
        self.chains = []
        for a, b in zip(self.edges, self.edges[1:]):
            mid = (a + b) / 2
            self.chains.append(tuple(n for s, e, n in iv if s <= mid < e))

    def chain(self, t: float) -> tuple:
        k = bisect.bisect_right(self.edges, t) - 1
        return self.chains[k] if 0 <= k < len(self.chains) else ()

    def pieces(self, a: float, b: float):
        """``(start, end, chain)`` of every piece of [a, b)."""
        k = max(bisect.bisect_right(self.edges, a) - 1, 0)
        t = a
        while t < b:
            if t < self.edges[0]:
                nxt, ch = min(self.edges[0], b), ()
            elif k >= len(self.chains):
                nxt, ch = b, ()
            else:
                nxt, ch = min(self.edges[k + 1], b), self.chains[k]
                k += 1
            if nxt > t:
                yield t, nxt, ch
            t = nxt


def innermost(chain: tuple) -> str:
    return label(chain[-1]) if chain else OUTSIDE


def phase(chain: tuple) -> str:
    """The engine phase in a chain: the timestep's direct child (the
    timestep itself where none)."""
    if not chain:
        return OUTSIDE
    return label(chain[1]) if len(chain) > 1 else chain[0]


def _merge(intervals) -> list:
    """The union of ``(start, end)`` intervals, as disjoint ones."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def offset_ns(rec: Recording) -> Tuple[float, int]:
    """(how far the device clock reads behind the host's, in ns; runs
    matched to an enqueue event).  Add it to a device time to read the
    host clock."""
    leads = [rec.enqueues[rid] - s for runs in rec.runs.values()
             for _, s, _, rid in runs if rid in rec.enqueues]
    if not leads:
        raise ValueError("no device run matches a host enqueue event")
    return float(np.quantile(leads, 1 - LEAD_QUANTILE)), len(leads)


def _median(values) -> Optional[float]:
    return float(np.median(values)) if len(values) else None


def reduce(rec: Recording) -> dict:
    steps = sorted((s, s + d, m) for n, s, d, m in rec.spans
                   if n == TIMESTEP)
    if not steps:
        raise ValueError("the trace holds no specpipe.timestep span")
    if not rec.runs:
        raise ValueError("the trace holds no TPU plane")
    t0, t1 = steps[0][0], max(e for _, e, _ in steps)
    off, matched = offset_ns(rec)
    cover = Cover(rec.spans)

    # host self time of each phase, per timestep
    execs = [(s, s + d) for n, s, d, _ in rec.spans
             if n.startswith(EXECUTOR)]
    self_ms = collections.defaultdict(list)
    span_ms = collections.defaultdict(list)
    for a, b, _ in steps:
        own = collections.defaultdict(float)
        full = collections.defaultdict(float)
        for n, s, d, _ in rec.spans:
            if not a <= s < b or cover.chain(s)[:2] != (TIMESTEP, n):
                continue
            inner = [(max(x, s), min(y, s + d)) for x, y in execs
                     if x < s + d and y > s]
            full[n] += d / 1e6
            own[n] += (d - sum(e - x for x, e in _merge(inner))) / 1e6
        for n in full:
            span_ms[n].append(full[n])
            self_ms[n].append(own[n])
    step_ms = [(b - a) / 1e6 for a, b, _ in steps]

    # device runs on the host clock, each attributed to its enqueue's spans
    chips = len(rec.runs)
    busy, idle, gaps = [], collections.Counter(), collections.Counter()
    by_phase, by_span = collections.Counter(), collections.Counter()
    device_s = collections.Counter()
    prog_s = collections.Counter()
    in_steps = 0
    for runs in rec.runs.values():
        iv = []
        for name, s, d, rid in runs:
            enq = rec.enqueues.get(rid)
            if enq is not None and t0 <= enq <= t1:
                chain = cover.chain(enq)
                by_phase[phase(chain)] += 1
                by_span[innermost(chain)] += 1
                device_s[phase(chain)] += d / 1e9
                in_steps += chain[:1] == (TIMESTEP,)
            s += off
            a, b = max(s, t0), min(s + d, t1)
            if b <= a:
                continue
            iv.append((a, b))
            prog_s[re.sub(r"\(\d+\)$", "", name).strip()] += (b - a) / 1e9
        merged = _merge(iv)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [t0] + [x for se in merged for x in se] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            for x, y, chain in cover.pieces(a, b):
                idle[innermost(chain)] += (y - x) / 1e9 / chips
                gaps[innermost(chain)] += 1
    window_s = (t1 - t0) / 1e9
    idle_s = sum(idle.values())
    return {
        "offset_ms": off / 1e6, "runs_matched": matched,
        "window_s": window_s, "busy_s": busy,
        "timesteps": len(steps), "timestep_ms": _median(step_ms),
        "phase_self_ms": {n: _median(v) for n, v in self_ms.items()},
        "phase_ms": {n: _median(v) for n, v in span_ms.items()},
        "programs_per_timestep": in_steps / len(steps) / chips,
        "programs_by_phase": dict(by_phase.most_common()),
        "programs_by_span": dict(by_span.most_common()),
        "device_s_by_phase": dict(device_s.most_common()),
        "device_ops": prog_s.most_common(12),
        "idle_s": idle_s,
        "idle_outside_share": idle.get(OUTSIDE, 0.0) / idle_s
        if idle_s else 0.0,
        "idle_gaps": [[f"{n} ({gaps[n]} gaps)", s]
                      for n, s in idle.most_common()],
    }


def metrics(red: dict) -> dict:
    """The per-layer numbers the reduction gives: each engine phase's
    median host self time per timestep, and the programs enqueued per
    timestep."""
    own = red["phase_self_ms"]
    return {"entry_host_ms": own.get("entry"),
            "expand_host_ms": own.get("expand"),
            "exit_host_ms": own.get("exit"),
            "programs_per_timestep": red["programs_per_timestep"]}


def main(argv: List[str]) -> int:
    import argparse

    from trace import find
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--save")
    ap.add_argument("--steps", type=int)
    args = ap.parse_args(argv)
    rec = load(find(args.trace_dir))
    if args.save:
        save(trim(rec, args.steps) if args.steps else rec, args.save)
    red = reduce(rec)
    print(f"device clock offset: {red['offset_ms']:.4f} ms from "
          f"{red['runs_matched']} runs", file=sys.stderr)
    print(json.dumps({**red, "metrics": metrics(red)}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
