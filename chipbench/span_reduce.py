"""Reduce the program's own spans in a profiler trace to what the serving
loop's per-layer numbers read.

``TailServer`` given a ``repro.obs.Recorder(annotate=
jax.profiler.TraceAnnotation)`` times each serving step as nested spans:
``server.step`` holds ``server.admit``, ``server.inputs`` (one
``server.frame`` per admitted request, holding ``server.parse``,
``server.decode`` and ``server.scatter``), ``server.tail`` and
``server.fetch``.  A running profiler records them on the host line of
the serving thread, beside the harness's own annotations
(``trace_reduce.ANNOTATIONS``) and in the device's timebase.

The window is ``trace_reduce``'s: from the first harness annotation's
start plus ``skip_s`` to the last one's end.  Within it:

* ``nodes``: the harness annotations and ``server.*`` spans that reach
  into the window, each with its parent by containment, and whether it
  lies wholly inside (only those are counted and timed);
* ``idle_by_path``: the device's idle time (the gaps between the union of
  its "XLA Ops" intervals, averaged over device planes), each gap
  attributed to the innermost span holding the gap's middle and keyed by
  the names from the outermost span down to it (``()`` where the host was
  in none);
* ``programs``: the device-program runs ("XLA Modules") wholly inside.

A trace of a program without these spans reduces to a summary whose
per-layer numbers are all ``None``.
"""
from __future__ import annotations

import bisect
from collections import defaultdict
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from chipbench.trace_reduce import (ANNOTATIONS, MODULE_LINE, OP_LINE,
                                    _events, _union, find_xplane)

PREFIX = "server."


@dataclass
class Node:
    name: str
    start_ns: int
    end_ns: int
    parent: int            # index of the innermost holding node, or -1
    inside: bool           # wholly inside the window

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclass
class SpanSummary:
    window_s: float
    busy_s: float
    programs: float
    nodes: list = field(default_factory=list)
    idle_by_path: dict = field(default_factory=dict)   # names -> s

    def spans(self, name: str) -> list:
        return [n for n in self.nodes if n.inside and n.name == name]

    def seconds(self, name: str) -> np.ndarray:
        return np.asarray([n.seconds for n in self.spans(name)])

    def idle_within(self, name: str) -> float:
        """Idle seconds whose gap middle lies inside a span ``name`` or
        any span it holds."""
        return sum(s for path, s in self.idle_by_path.items() if name in path)

    # ---------------------------------------------- per-layer numbers ----
    def inputs_ms_per_request_p50(self) -> Optional[float]:
        """Per step, ``server.inputs`` over the ``server.frame`` spans it
        holds; the median over steps, in ms."""
        frames = defaultdict(int)
        for n in self.nodes:
            if n.name == "server.frame" and n.parent >= 0:
                frames[n.parent] += 1
        per = [self.nodes[i].seconds / k for i, k in frames.items()
               if self.nodes[i].inside
               and self.nodes[i].name == "server.inputs"]
        return 1e3 * float(np.median(per)) if per else None

    def fetch_ms_p50(self) -> Optional[float]:
        """Median ``server.fetch``, in ms: the host waits there for the
        device to finish and the logits to arrive."""
        s = self.seconds("server.fetch")
        return 1e3 * float(np.median(s)) if s.size else None

    def idle_in_share(self, name: str) -> Optional[float]:
        """Device idle time inside ``name`` (what it holds included) over
        the window, in %."""
        if not self.spans(name):
            return None
        return 100.0 * self.idle_within(name) / self.window_s

    def programs_per_request(self) -> Optional[float]:
        """Device-program runs per ``server.frame`` in the window."""
        frames = len(self.spans("server.frame"))
        return self.programs / frames if frames else None

    def readings(self) -> dict:
        """The per-layer numbers, by the names of their metrics."""
        return {"inputs_ms_per_request_p50": self.inputs_ms_per_request_p50(),
                "fetch_ms_p50": self.fetch_ms_p50(),
                "idle_in_inputs_share": self.idle_in_share("server.inputs"),
                "programs_per_request": self.programs_per_request()}


def window(host: list, skip_s: float = 0.0) -> tuple:
    """``(lo, hi)`` in ns by ``trace_reduce.summarize``'s rule: the harness
    annotations' span, less its first ``skip_s``."""
    marks = [ev for ev in host if ev[0] in ANNOTATIONS]
    if not marks:
        raise ValueError(f"no host annotation among {ANNOTATIONS} "
                         "in the trace")
    lo = min(ev[1] for ev in marks) + int(skip_s * 1e9)
    hi = max(ev[2] for ev in marks)
    if hi <= lo:
        raise ValueError("the annotated span is shorter than skip_s")
    return lo, hi


def nest(spans: list) -> tuple:
    """``(name, start_ns, end_ns)`` spans, sorted by start (the outer one
    first where two start together), and for each the index of the
    innermost span that holds it, or -1."""
    order = sorted(spans, key=lambda s: (s[1], -s[2]))
    parents, stack = [], []
    for i, (_, _, end) in enumerate(order):
        while stack and order[stack[-1]][2] < end:
            stack.pop()
        parents.append(stack[-1] if stack else -1)
        stack.append(i)
    return order, parents


def innermost(order: list, parents: list, starts: list, t: int) -> int:
    """Index of the innermost span of ``nest``'s result that holds time
    ``t`` (start <= t <= end), or -1; ``starts`` are the spans' starts.
    The last span to start by ``t`` holds it, or else the innermost of
    its holders that does."""
    k = bisect.bisect_right(starts, t) - 1
    while k >= 0 and order[k][2] < t:
        k = parents[k]
    return k


def idle_gaps(ops: list, lo: int, hi: int) -> list:
    """``[start, end)`` gaps in ``[lo, hi)`` outside every operation of
    one device."""
    iv = [(max(s, lo), min(e, hi)) for _, s, e in ops
          if min(e, hi) > max(s, lo)]
    merged = _union(np.asarray(iv, np.int64).reshape(-1, 2))
    edges = np.concatenate(([lo], merged.ravel(), [hi])).reshape(-1, 2)
    return [(int(s), int(e)) for s, e in edges if e > s]


def reduce_events(host: list, devices: list,
                  skip_s: float = 0.0) -> SpanSummary:
    """The summary from plain events: ``host`` the ``(name, start_ns,
    end_ns)`` events of the host lines, ``devices`` one ``(ops, modules)``
    pair of such events per device."""
    if not devices:
        raise ValueError("no device plane with an 'XLA Ops' line in the trace")
    lo, hi = window(host, skip_s)
    order, parents = nest([
        ev for ev in host if ev[2] > lo and ev[1] < hi
        and (ev[0] in ANNOTATIONS or ev[0].startswith(PREFIX))])
    starts = [s for _, s, _ in order]
    paths = []
    for (name, _, _), p in zip(order, parents):
        paths.append((paths[p] if p >= 0 else ()) + (name,))
    idle = defaultdict(float)
    busy, programs = 0.0, 0
    for ops, modules in devices:
        programs += sum(1 for _, s, e in modules if lo <= s and e <= hi)
        gaps = idle_gaps(ops, lo, hi)
        busy += (hi - lo - sum(e - s for s, e in gaps)) * 1e-9
        for s, e in gaps:
            k = innermost(order, parents, starts, (s + e) // 2)
            idle[paths[k] if k >= 0 else ()] += (e - s) * 1e-9
    n = len(devices)
    nodes = [Node(name, s, e, p, lo <= s and e <= hi)
             for (name, s, e), p in zip(order, parents)]
    return SpanSummary(window_s=(hi - lo) * 1e-9, busy_s=busy / n,
                       programs=programs / n, nodes=nodes,
                       idle_by_path={k: v / n for k, v in idle.items()})


def summarize(pd, skip_s: float = 0.0) -> SpanSummary:
    """Reduce a loaded ``jax.profiler.ProfileData``."""
    host, devices = [], []
    for plane in pd.planes:
        lines = {l.name: l for l in plane.lines}
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                host += _events(line)
        elif plane.name.startswith("/device:") and OP_LINE in lines:
            devices.append((_events(lines[OP_LINE]),
                            _events(lines[MODULE_LINE])
                            if MODULE_LINE in lines else []))
    return reduce_events(host, devices, skip_s)


def summarize_dir(log_dir: str, skip_s: float = 0.0) -> SpanSummary:
    from jax.profiler import ProfileData
    return summarize(ProfileData.from_file(find_xplane(log_dir)),
                     skip_s=skip_s)
