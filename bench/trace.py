"""Reduce a profiler trace to device busy time, time per program and idle
gaps labelled by the host spans the benchmark records.

``load`` reads the ``.xplane.pb`` the JAX profiler writes into plain
tuples; ``reduce`` works on those tuples only, so the recorded trace kept
beside the tests checks it without a chip.

Device busy time is the union of the intervals of the device's program
executions (the "XLA Modules" line of each TPU plane; "XLA Ops" where a
plane has no module line).  The window runs from the start of the first
``bench.timestep`` host span to the end of the last.  An idle gap is a
stretch of the window in which the device ran nothing; it is labelled
by the innermost ``bench.*`` host span at its midpoint (``timestep``
alone means the engine's own host work between executor calls).

A launch span (``bench.launch.<label>``) wraps a call that launches one
compiled program.  Programs run on a chip in the order they were
launched, so the k-th launch span pairs with the k-th run of the one
program name whose runs match the launch spans in number and order;
their device time is summed by label (``launched_s``), which tells the
target's programs from the draft's where the program gives both one
name.  Where no name matches, nothing is paired.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import gzip
import itertools
import json
import os
import re
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]          # name, start_ns, duration_ns

DEVICE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINES = ("XLA Modules", "XLA Ops")
LAUNCH = "bench.launch."
# launch spans and program runs that ``pair_launches`` lets go unpaired at
# the trace's ends, the most program names it combines, and how far (ns)
# a run may start before its span on the device's clock
SLACK = 4
MAX_NAMES = 3
CLOCK_SLACK_NS = 20e6


@dataclasses.dataclass
class Plane:
    name: str
    lines: Dict[str, List[Event]]


def save(planes: List[Plane], path: str) -> None:
    """``load``'s tuples as gzipped JSON (the recorded test trace)."""
    with gzip.open(path, "wt") as f:
        json.dump([dataclasses.asdict(p) for p in planes], f)


def restore(path: str) -> List[Plane]:
    with gzip.open(path, "rt") as f:
        return [Plane(p["name"], {k: [tuple(e) for e in v]
                                  for k, v in p["lines"].items()})
                for p in json.load(f)]


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> List[Plane]:
    """The device planes' program lines and the host's bench spans."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        if DEVICE.match(plane.name):
            by_name = {line.name: line for line in plane.lines}
            name = next((n for n in MODULE_LINES if n in by_name), None)
            if name is not None:
                lines[name] = [(e.name, e.start_ns, e.duration_ns)
                               for e in by_name[name].events]
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                ev = [(e.name, e.start_ns, e.duration_ns)
                      for e in line.events if e.name.startswith("bench.")]
                if ev:
                    lines[line.name] = ev
        if lines:
            out.append(Plane(plane.name, lines))
    return out


def program_name(event_name: str) -> str:
    """``jit_foo(12345)`` -> ``jit_foo``."""
    return re.sub(r"\(\d+\)$", "", event_name).strip()


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


@dataclasses.dataclass
class Summary:
    """Per traced window: seconds, device busy seconds per chip, device
    seconds and calls per program (summed over chips, and per chip),
    idle seconds and gap counts per host label (averaged over chips)."""

    window_s: float
    busy_s: List[float]
    program_s: Dict[str, float]
    program_calls: Dict[str, int]
    program_s_per_chip: Dict[str, List[float]]
    idle_s: Dict[str, float]
    idle_gaps: Dict[str, int]
    launched_s: Dict[str, float] = dataclasses.field(default_factory=dict)
    launched_calls: Dict[str, int] = dataclasses.field(default_factory=dict)

    @property
    def chips(self) -> int:
        return len(self.busy_s)

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.program_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[f"{n} ({self.idle_gaps[n]} gaps)", s]
                              for n, s in gaps]}


def _align(launches, runs, skip):
    """Pair ``launches`` with ``runs[skip:]`` one to one in order, or
    None where the pairing breaks a rule of ``pair_launches``."""
    label_of = {}
    pairs = list(zip(launches, runs[skip:]))
    for (t, label), run in pairs:
        if run[1] < t - CLOCK_SLACK_NS or label_of.setdefault(
                run[0], label) != label:
            return None
    return pairs


def pair_launches(launches, runs):
    """``launches`` [(start, label)] (host clock) and one chip's program
    ``runs`` [(name, start, duration)] (device clock): ``{label: [runs]}``,
    or ``{}``.

    Programs run on a chip in the order they were launched, so the k-th
    launch span pairs with the k-th run of the programs the spans launch.
    Those are found by order, not by time, since the two clocks disagree
    by more than a short program lasts (a v5e's device clock reads about
    1.8 ms early): a set of at most ``MAX_NAMES`` program names whose runs,
    taken together, line up with the spans one to one, where every
    compiled program (a name with its fingerprint) pairs with one label
    only and no run starts well before its span.  At the trace's ends up
    to ``SLACK`` runs (launched before the first span, or after the last)
    and up to ``SLACK`` spans (whose programs ran after the trace) may go
    unpaired.  Of several such sets the one that leaves fewest unpaired,
    then the one of fewest names, wins; ``{}`` where none, or two alike,
    exist."""
    launches = sorted(launches)
    if not launches:
        return {}
    by_name = collections.defaultdict(list)
    for ev in runs:
        by_name[program_name(ev[0])].append(ev)
    names = sorted(n for n, rs in by_name.items()
                   if len(rs) <= len(launches) + SLACK)
    found = []
    for k in range(1, MAX_NAMES + 1):
        for group in itertools.combinations(names, k):
            total = sum(len(by_name[n]) for n in group)
            if abs(total - len(launches)) > 2 * SLACK:
                continue
            rs = sorted((ev for n in group for ev in by_name[n]),
                        key=lambda ev: ev[1])
            for skip in range(min(SLACK, len(rs)) + 1):
                left = abs(len(rs) - skip - len(launches))
                if left > SLACK:
                    continue
                pairs = _align(launches, rs, skip)
                if pairs is not None:
                    found.append((skip + left, k, pairs))
    found.sort(key=lambda f: f[:2])
    if not found or (len(found) > 1 and found[1][:2] == found[0][:2]):
        return {}
    out = collections.defaultdict(list)
    for (_, label), run in found[0][2]:
        out[label].append(run)
    return dict(out)


def reduce(planes: List[Plane]) -> Summary:
    host = [ev for p in planes if not DEVICE.match(p.name)
            for line in p.lines.values() for ev in line]
    steps = [(s, s + d) for n, s, d in host if n == "bench.timestep"]
    if not steps:
        raise ValueError("the trace holds no bench.timestep span")
    t0, t1 = min(s for s, _ in steps), max(e for _, e in steps)
    spans = sorted(((s, s + d, n[len("bench."):]) for n, s, d in host
                    if n != "bench.timestep"), key=lambda x: x[0])
    launches = [(s, n[len(LAUNCH):]) for n, s, d in host
                if n.startswith(LAUNCH) and t0 <= s <= t1]
    step_spans = sorted(steps)

    def label(t):
        inner = [(e - s, n) for s, e, n in spans if s <= t <= e]
        if inner:
            return min(inner)[1]
        if any(s <= t <= e for s, e in step_spans):
            return "engine host work"
        return "between timesteps"

    devices = [p for p in planes if DEVICE.match(p.name)]
    if not devices:
        raise ValueError("the trace holds no TPU plane")
    busy, idle, gaps = [], collections.Counter(), collections.Counter()
    prog_s, calls = collections.Counter(), collections.Counter()
    per_chip = collections.defaultdict(lambda: [0.0] * len(devices))
    launched_s, launched_calls = collections.Counter(), collections.Counter()
    for k, p in enumerate(devices):
        line = next(p.lines[n] for n in MODULE_LINES if n in p.lines)
        paired = pair_launches(launches, [
            ev for ev in line
            if t0 - CLOCK_SLACK_NS <= ev[1] <= t1 + CLOCK_SLACK_NS])
        label_of = {(n, s): lab for lab, evs in paired.items()
                    for n, s, _ in evs}
        iv = []
        for n, s, d in line:
            lab = label_of.get((n, s))
            s, e = max(s, t0), min(s + d, t1)
            if e <= s:
                continue
            iv.append((s, e))
            name = program_name(n)
            if lab is not None:
                name = f"{name} [{lab}]"
                launched_s[lab] += (e - s) / 1e9 / len(devices)
                launched_calls[lab] += 1
            prog_s[name] += (e - s) / 1e9
            per_chip[name][k] += (e - s) / 1e9
            calls[name] += 1
        merged = _union(iv)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [t0] + [x for se in merged for x in se] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                lab = label((a + b) / 2)
                idle[lab] += (b - a) / 1e9 / len(devices)
                gaps[lab] += 1
    return Summary(window_s=(t1 - t0) / 1e9, busy_s=busy,
                   program_s=dict(prog_s), program_calls=dict(calls),
                   program_s_per_chip=dict(per_chip), idle_s=dict(idle),
                   idle_gaps=dict(gaps), launched_s=dict(launched_s),
                   launched_calls=dict(launched_calls))
