"""Reduce a JAX profiler trace (``.xplane.pb``) to what the metrics read.

The traced window is the span of the harness's own host annotations
(``serve_step``, ``submit``, ``wait_arrivals``).  Within it:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device (its "XLA Ops" line), averaged over the device planes;
* ``modules``: per device program (the "XLA Modules" line, named without
  the trailing fingerprint, as ``jit__lambda``), its count of runs and
  their device seconds;
* ``ops``: per ``(program, operation)`` pair, the operation's count and
  device seconds, an operation belonging to the program run whose
  interval holds it and named by its HLO instruction (``fusion.4``,
  ``bottleneck_decompress.1``); ``op_kinds`` gives each one's result type
  and opcode.  Both count only runs and operations that lie wholly inside
  the window, so a count and its seconds always belong together;
* ``idle_by_host``: the device's idle time, each gap attributed to the
  host annotation that holds the gap's middle (``"none"`` where the host
  was in none of them), and the longest gaps.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

ANNOTATIONS = ("serve_step", "submit", "wait_arrivals")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
N_GAPS = 10


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    n_devices: int
    modules: dict = field(default_factory=dict)    # name -> [count, s]
    ops: dict = field(default_factory=dict)        # (module, op) -> [count, s]
    op_kinds: dict = field(default_factory=dict)   # (module, op) -> "type opcode"
    idle_by_host: dict = field(default_factory=dict)   # activity -> s
    longest_gaps: list = field(default_factory=list)   # [activity, s]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def module_seconds(self, match) -> tuple:
        """``(runs, seconds)`` of the programs whose name ``match`` accepts."""
        hits = [v for k, v in self.modules.items() if match(k)]
        return sum(c for c, _ in hits), sum(s for _, s in hits)

    def op_seconds(self, match) -> tuple:
        """``(count, seconds)`` of the operations for which
        ``match(module, op)`` is true."""
        hits = [v for k, v in self.ops.items() if match(*k)]
        return sum(c for c, _ in hits), sum(s for _, s in hits)

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` operations with the most device time, as
        ``[program/instruction type opcode, seconds]``."""
        top = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:n]
        return [[f"{m}/{op} {self.op_kinds.get((m, op), '')}".rstrip(), s]
                for (m, op), (_, s) in top]


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: np.ndarray) -> np.ndarray:
    """Merge ``(n, 2)`` [start, end) intervals into disjoint sorted ones."""
    if not len(intervals):
        return intervals.reshape(0, 2)
    iv = intervals[np.argsort(intervals[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.int64)


def _events(line):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def module_name(name: str) -> str:
    """``jit__lambda(5344685735798289724)`` -> ``jit__lambda``."""
    return name.split("(", 1)[0]


def op_name(text: str) -> tuple:
    """An "XLA Ops" event's HLO text, ``%fusion.4 = bf16[16,56]{...}
    fusion(...), ...``, as ``("fusion.4", "bf16[16,56] fusion")``."""
    name, _, rest = text.partition(" = ")
    end, depth = 0, 0                   # the result type ends at a space
    for end, ch in enumerate(rest):     # outside any parenthesis
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if ch == " " and depth == 0:
            break
    rtype = re.sub(r"\{[^}]*\}", "", rest[:end])
    opcode = rest[end + 1:].split("(", 1)[0]
    return name.lstrip("%"), f"{rtype} {opcode}".strip()


def summarize(pd, skip_s: float = 0.0) -> TraceSummary:
    """Reduce a loaded ``jax.profiler.ProfileData``.  ``skip_s`` leaves out
    the first seconds of the annotated span."""
    host = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [ev for ev in _events(line) if ev[0] in ANNOTATIONS]
    if not host:
        raise ValueError(f"no host annotation among {ANNOTATIONS} in the trace")
    host.sort(key=lambda ev: ev[1])
    lo = host[0][1] + int(skip_s * 1e9)
    hi = max(ev[2] for ev in host)
    if hi <= lo:
        raise ValueError("the annotated span is shorter than skip_s")
    starts = [ev[1] for ev in host]
    devices = [p for p in pd.planes if p.name.startswith("/device:")
               and any(l.name == OP_LINE for l in p.lines)]
    if not devices:
        raise ValueError("no device plane with an 'XLA Ops' line in the trace")
    modules = defaultdict(lambda: [0, 0.0])
    ops = defaultdict(lambda: [0, 0.0])
    kinds = {}
    idle = defaultdict(float)
    gaps = []
    busy = 0.0
    for plane in devices:
        lines = {l.name: l for l in plane.lines}
        mods = [(module_name(n), s, e) for n, s, e in _events(lines[MODULE_LINE])
                if e > lo and s < hi] if MODULE_LINE in lines else []
        mods.sort(key=lambda ev: ev[1])
        mstarts = [ev[1] for ev in mods]
        for name, s, e in mods:
            if lo <= s and e <= hi:
                modules[name][0] += 1
                modules[name][1] += (e - s) * 1e-9
        iv = []
        for text, s, e in _events(lines[OP_LINE]):
            if min(e, hi) > max(s, lo):
                iv.append((max(s, lo), min(e, hi)))
            if s < lo or e > hi:
                continue
            k = bisect.bisect_right(mstarts, s) - 1
            owner = mods[k][0] if k >= 0 and mods[k][2] >= e else "none"
            name, kinds[(owner, name)] = op_name(text)[0], op_name(text)[1]
            ops[(owner, name)][0] += 1
            ops[(owner, name)][1] += (e - s) * 1e-9
        merged = _union(np.asarray(iv, np.int64).reshape(-1, 2))
        busy += float((merged[:, 1] - merged[:, 0]).sum()) * 1e-9
        edges = np.concatenate(([lo], merged.ravel(), [hi])).reshape(-1, 2)
        for s, e in edges:
            if e <= s:
                continue
            # the annotations come from one thread and do not nest: the
            # one that holds the gap's middle is the last to start before it
            mid = (s + e) // 2
            k = bisect.bisect_right(starts, mid) - 1
            act = host[k][0] if k >= 0 and host[k][2] >= mid else "none"
            idle[act] += (e - s) * 1e-9
            gaps.append([act, (e - s) * 1e-9])
    n = len(devices)
    gaps.sort(key=lambda g: -g[1])
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy / n, n_devices=n,
        modules={k: [c, s / n] for k, (c, s) in modules.items()},
        ops={k: [c, s / n] for k, (c, s) in ops.items()}, op_kinds=kinds,
        idle_by_host={k: v / n for k, v in idle.items()},
        longest_gaps=gaps[:N_GAPS])


def summarize_dir(log_dir: str, skip_s: float = 0.0) -> TraceSummary:
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(find_xplane(log_dir)),
                     skip_s=skip_s)
