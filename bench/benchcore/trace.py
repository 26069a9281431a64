"""Reduction of a profiler trace to the numbers the per-layer readers use.

`load` turns the `.xplane.pb` that `jax.profiler` wrote into plain lists:
device operations and device program executions of the first TPU, and
the host spans the harness opened (names starting with `bench.`), all as
(name, start_ns, duration_ns) on the trace's one clock. Everything else
here works on those lists, so a test can run it on a recorded excerpt.

The window a reduction covers is the traced time in which the harness had
work: from the first `bench.step` span's start to the last one's end,
less the `bench.wait` spans (no request active, waiting for the next due
time).
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

Event = Tuple[str, int, int]            # name, start_ns, duration_ns
NAME_CHARS = 120        # an op's name is its HLO text; keep the head

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."


def load(trace_dir: str) -> Dict[str, List[Event]]:
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = jax.profiler.ProfileData.from_file(paths[0])
    out: Dict[str, List[Event]] = {"ops": [], "modules": [], "host": []}
    devices = sorted(p.name for p in pd.planes
                     if p.name.startswith(DEVICE_PREFIX))
    for plane in pd.planes:
        if devices and plane.name == devices[0]:
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    out[key].extend((e.name[:NAME_CHARS], int(e.start_ns),
                                     int(e.duration_ns))
                                    for e in line.events)
        elif not plane.name.startswith("/device:"):
            for line in plane.lines:
                out["host"].extend((e.name, int(e.start_ns),
                                    int(e.duration_ns))
                                   for e in line.events
                                   if e.name.startswith(HOST_PREFIX))
    for v in out.values():
        v.sort(key=lambda e: e[1])
    return out


def op_name(hlo: str) -> str:
    """`%ragged_decode_attention.6 = bf16[...] ...` -> the instruction's
    name without its numeric suffix."""
    head = hlo.split(" ", 1)[0].lstrip("%")
    base, _, suffix = head.rpartition(".")
    return base if base and suffix.isdigit() else head


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[List[int]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def clip(intervals, lo: int, hi: int) -> List[Tuple[int, int]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def total(intervals) -> int:
    return sum(b - a for a, b in intervals)


def window(host: Sequence[Event]) -> Tuple[int, int, List[Tuple[int, int]]]:
    """(start, end, waits) of the traced time with work."""
    steps = [e for e in host if e[0] == HOST_PREFIX + "step"]
    if not steps:
        raise ValueError("no bench.step span in the trace")
    lo = steps[0][1]
    hi = max(s + d for _, s, d in steps)
    waits = clip([(s, s + d) for n, s, d in host
                  if n == HOST_PREFIX + "wait"], lo, hi)
    return lo, hi, waits


class Reduced:
    """A trace's lists with the derived quantities the readers need."""

    def __init__(self, ev: Dict[str, List[Event]]):
        self.ev = ev
        self.lo, self.hi, self.waits = window(ev["host"])
        self.active_ns = (self.hi - self.lo) - total(union(self.waits))
        ops = clip([(s, s + d) for _, s, d in ev["ops"]], self.lo, self.hi)
        self.busy = union(ops)
        self.busy_ns = total(self.busy) - sum(
            total(clip(self.busy, a, b)) for a, b in union(self.waits))

    def kernel_seconds(self, kernel: str) -> Tuple[float, int]:
        """(seconds, calls) of a Pallas kernel: device ops whose HLO
        instruction is named after the kernel (`%<kernel>.<n> = ...`)."""
        hits = [d for n, s, d in self.ev["ops"]
                if op_name(n) == kernel and self.lo <= s < self.hi]
        return sum(hits) * 1e-9, len(hits)

    def step_programs(self) -> List[int]:
        """Per `bench.step` span, the device time (ns) of every program
        that started from its start to the next span's: the fused
        serving step and whatever else the step ran (admission's copies),
        so work that moves into a program of its own stays counted. The
        engine jits `functools.partial` objects, which the trace names
        all alike (`jit__unknown(<fingerprint>)`), so no program can be
        picked out by name. Steps that ran no program give none; a trace
        with steps and device ops but no programs is an error."""
        mods = self.ev["modules"]
        steps = [(s, d) for n, s, d in self.ev["host"]
                 if n == HOST_PREFIX + "step"]
        if steps and self.ev["ops"] and not mods:
            raise ValueError(f"no {MODULES_LINE!r} events in the trace")
        out = []
        for k, (s, _) in enumerate(steps):
            end = steps[k + 1][0] if k + 1 < len(steps) else self.hi
            t = sum(md for _, ms, md in mods if s <= ms < end)
            if t:
                out.append(t)
        return out

    def top_ops(self, n: int = 10) -> List[list]:
        """Device ops by self time: an op that encloses others (a loop)
        is left out, its body is counted."""
        agg: Dict[str, int] = defaultdict(int)
        ops = [e for e in self.ev["ops"] if self.lo <= e[1] < self.hi]
        for k, (name, s, d) in enumerate(ops):
            if k + 1 < len(ops) and ops[k + 1][1] < s + d:
                continue
            agg[name] += d
        top = sorted(agg.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> List[list]:
        """The longest device-idle gaps inside the window (waits for the
        next due request left out), each named by the innermost harness
        span that covers its middle."""
        free, prev = [], self.lo
        for a, b in self.busy + [(self.hi, self.hi)]:
            if a > prev:
                free.append((prev, a))
            prev = max(prev, b)
        gaps = []
        for a, b in free:
            for wa, wb in union(self.waits):
                if wa <= a < wb:
                    a = wb
                if a < wa < b:
                    gaps.append((a, wa))
                    a = wb
            if a < b:
                gaps.append((a, b))
        spans = [e for e in self.ev["host"] if e[0] != HOST_PREFIX + "step"]
        steps = [e for e in self.ev["host"] if e[0] == HOST_PREFIX + "step"]
        out = []
        for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
            mid = (a + b) // 2
            label = "none"
            for group in (steps, spans):       # innermost wins
                for name, s, d in group:
                    if s <= mid < s + d:
                        label = name
            out.append([label, (b - a) * 1e-9])
        return out
