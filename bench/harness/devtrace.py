"""Reduce a profiler trace (``.xplane.pb``) to device metrics.

Each chip is a plane named ``/device:TPU:<n>``.  Its ``XLA Ops`` line
holds one event per operation run on the chip, its ``XLA Modules`` line
one event per program call, named after the jitted function
(``jit_mixed_step``, ``jit_burst``, ...).  From these:

- busy time: the union of the operation intervals, and the gaps
  between them;
- device time and calls per program;
- time in collectives (all-reduce, all-gather, reduce-scatter,
  all-to-all, collective-permute), per chip;
- the operations that took most time (loops that contain other
  operations left out).

Idle gaps on the first chip are named by the host activity (a
``TraceMe`` event on a host thread) that overlaps them most.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|allreduce|allgather", re.I)
# operations that contain others on the same line (a scan's loop): they
# count towards busy time, not towards the time of an operation
CONTAINER = re.compile(r"^(while|conditional|call)\b")
_SHAPE = re.compile(r"[a-z]+\d*\[[\d,]*\]")


def op_label(name: str) -> str:
    """``%fusion.183 = (f32[64,5,3,32]{...}, ...) fusion(...)`` ->
    ``fusion.183 f32[64,5,3,32]``: the operation and its first shape."""
    head, _, rest = name.partition(" = ")
    label = head.strip().lstrip("%")
    m = _SHAPE.search(rest)
    return f"{label} {m.group(0)}" if m else label


@dataclasses.dataclass
class Chip:
    name: str
    busy_ns: float
    op_ns: Dict[str, float]                    # by op_label
    programs: Dict[str, Tuple[float, int]]     # name -> (ns, calls)
    collective_ns: float
    gaps: List[Tuple[int, int]]                # idle (start, end) ns


@dataclasses.dataclass
class Reduction:
    chips: List[Chip]
    idle_gaps: List[Tuple[str, float]]         # (host activity, s)

    def program(self, key: str) -> Tuple[float, int]:
        """(device seconds averaged over chips, calls on the first chip)
        of the programs whose name contains ``key``; (0, 0) if none."""
        secs, calls = [], 0
        for i, c in enumerate(self.chips):
            ns = sum(v[0] for k, v in c.programs.items() if key in k)
            secs.append(ns / 1e9)
            if i == 0:
                calls = sum(v[1] for k, v in c.programs.items() if key in k)
        return (sum(secs) / len(secs) if secs else 0.0), calls

    @property
    def busy_s(self) -> float:
        return sum(c.busy_ns for c in self.chips) / len(self.chips) / 1e9

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        tot: Dict[str, float] = collections.Counter()
        for c in self.chips:
            for k, v in c.op_ns.items():
                tot[k] += v / len(self.chips) / 1e9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[int, int]]):
    """Merged intervals and the gaps between them."""
    merged: List[List[int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    return merged, gaps


def _events(line):
    for e in line.events:
        yield e.name, int(e.start_ns), int(e.duration_ns)


def reduce(data, n_gaps: int = 10) -> Reduction:
    """``data``: a ``jax.profiler.ProfileData``."""
    chips, host = [], []
    for plane in data.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:TPU:") and "XLA Ops" in lines:
            ivs, op_ns, coll = [], collections.Counter(), 0.0
            for name, s, d in _events(lines["XLA Ops"]):
                ivs.append((s, s + d))
                label = op_label(name)
                if not CONTAINER.match(label):
                    op_ns[label] += d
                if COLLECTIVE.search(label):
                    coll += d
            merged, gaps = _union(ivs)
            progs: Dict[str, List[float]] = {}
            if "XLA Modules" in lines:
                for name, s, d in _events(lines["XLA Modules"]):
                    key = name.split("(")[0]
                    p = progs.setdefault(key, [0.0, 0])
                    p[0] += d
                    p[1] += 1
            chips.append(Chip(
                name=plane.name,
                busy_ns=float(sum(e - s for s, e in merged)),
                op_ns=dict(op_ns),
                programs={k: (v[0], int(v[1])) for k, v in progs.items()},
                collective_ns=coll, gaps=gaps))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [(s, s + d, name) for name, s, d in _events(ln)
                         if d > 0]
    chips.sort(key=lambda c: int(re.sub(r"\D", "", c.name) or 0))
    gaps = []
    if chips:
        longest = sorted(chips[0].gaps, key=lambda g: g[0] - g[1])[:n_gaps]
        gaps = [(_host_activity(host, s, e), (e - s) / 1e9)
                for s, e in longest]
    return Reduction(chips=chips, idle_gaps=gaps)


def _host_activity(host, s: int, e: int) -> str:
    """The host event that covers most of [s, e); among those covering
    half of it or more, the shortest (the innermost)."""
    best: Optional[Tuple[float, float, str]] = None
    for hs, he, name in host:
        ov = min(he, e) - max(hs, s)
        if ov <= 0:
            continue
        cover = ov / max(1, e - s)
        key = (min(cover, 0.5), -(he - hs) if cover >= 0.5 else ov)
        if best is None or key > best[:2]:
            best = (key[0], key[1], name)
    return best[2] if best else "no host event"
