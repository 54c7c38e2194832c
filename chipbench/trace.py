"""From the profiler's ``.xplane.pb`` to numbers, with nothing but JAX.

What a TPU trace holds (looked at by hand, PR 24): one plane a chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has an event for every HLO
instruction that ran, named by the instruction's full text
(``%fusion.15 = f32[1081344,100]{...} fusion(f32[2449029,100]{...} %copy.205, ...)``),
and whose line ``XLA Modules`` has one event a program execution. A
``while`` is an event that spans its body's events, so durations are
summed as SELF time. The host's threads are the lines of ``/host:CPU``, on
the same clock; ``jax.profiler.TraceAnnotation`` spans land there.

The scope of an instruction (``jit(step)/jvp(qt_sample_hop2)/sort``) is
not in the trace: ``scopes_of`` reads it from the compiled program's HLO
text, where every instruction carries ``metadata={op_name="..."}``.
"""

from __future__ import annotations

import bisect
import collections
import re

WINDOW = "chipbench.window"
_INSTR = re.compile(r"^%([\w.\-]+) = ")
_META = re.compile(
    r'^\s*(?:ROOT )?%([\w.\-]+) = .*?metadata=\{op_name="([^"]*)"', re.M)


def scopes_of(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` of a compiled module's text."""
    return {m.group(1): m.group(2) for m in _META.finditer(hlo_text)}


class Op:
    __slots__ = ("name", "text", "start", "end", "self_s", "scope")

    def __init__(self, text, start, end):
        m = _INSTR.match(text)
        self.name = m.group(1) if m else text[:40]
        self.text = text
        self.start, self.end = start, end
        self.self_s = end - start
        self.scope = ""


def _self_times(ops):
    """Subtract from every op the time of the ops nested in it."""
    ops.sort(key=lambda o: (o.start, -o.end))
    stack = []
    for op in ops:
        while stack and stack[-1].end <= op.start:
            stack.pop()
        if stack:
            stack[-1].self_s -= min(op.end, stack[-1].end) - op.start
        stack.append(op)
    for op in ops:
        op.self_s = max(op.self_s, 0.0)
    return ops


def _union(intervals):
    """Disjoint sorted intervals covering the same time."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


class Trace:
    """One traced window: ``devices`` is a list (one a chip) of ``Op``
    lists clipped to the window, times in seconds on the trace's clock."""

    def __init__(self, path: str, scopes: dict | None = None, chips=None):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        self.host = []              # (thread, name, start, end)
        planes = {}
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                planes[int(plane.name.rsplit(":", 1)[1])] = plane
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for ev in line.events:
                        self.host.append((line.name, ev.name,
                                          ev.start_ns * 1e-9,
                                          (ev.start_ns + ev.duration_ns) * 1e-9))
        marks = [h for h in self.host if h[1] == WINDOW]
        raw = []
        for idx in sorted(planes)[:chips]:
            ops = []
            for line in planes[idx].lines:
                if line.name == "XLA Ops":
                    ops = [Op(ev.name, ev.start_ns * 1e-9,
                              (ev.start_ns + ev.duration_ns) * 1e-9)
                           for ev in line.events]
            raw.append(_self_times(ops))
        if marks:
            self.t0, self.t1 = marks[0][2], marks[0][3]
        else:
            every = [o for ops in raw for o in ops]
            self.t0 = min((o.start for o in every), default=0.0)
            self.t1 = max((o.end for o in every), default=0.0)
        scopes = scopes or {}
        self.devices = []
        for ops in raw:
            kept = [o for o in ops if o.end > self.t0 and o.start < self.t1]
            for o in kept:
                o.scope = scopes.get(o.name, "")
            self.devices.append(kept)

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    def busy(self, ops):
        return _union([(max(o.start, self.t0), min(o.end, self.t1))
                       for o in ops])

    @property
    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(sum(e - s for s, e in self.busy(ops))
                   for ops in self.devices) / len(self.devices)

    def seconds(self, pick) -> float:
        """Self time of the ops ``pick(op)`` accepts, averaged over the
        chips; None where no op matches."""
        found, total = False, 0.0
        for ops in self.devices:
            for o in ops:
                if pick(o):
                    found = True
                    total += o.self_s
        return total / len(self.devices) if found else None

    def top_ops(self, n=10):
        """The ops that took most self time on chip 0, grouped by their
        ``qt_*`` scope where they have one, else by instruction name and
        the head of its op_name."""
        groups = collections.Counter()
        for o in self.devices[0] if self.devices else []:
            m = re.search(r"qt_\w+", o.scope)
            if m:
                key = m.group(0)
            else:
                head = "/".join(o.scope.split("/")[1:3])
                key = f"{o.name} [{head}]" if head else o.name
            groups[key] += o.self_s
        return [[k, v] for k, v in groups.most_common(n)]

    def idle_gaps(self, n=10):
        """The idle time of chip 0 inside the window by what the host was
        doing in each gap: the host span that overlaps the gap most (the
        shortest such, so the innermost), as ``thread/span``."""
        if not self.devices:
            return []
        busy = self.busy(self.devices[0])
        edges = [self.t0] + [t for iv in busy for t in iv] + [self.t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] - edges[i] > 1e-6]
        gaps.sort(key=lambda g: g[0] - g[1])
        host = sorted((h for h in self.host if h[1] != WINDOW),
                      key=lambda h: h[2])
        starts = [h[2] for h in host]
        by = collections.Counter()
        for s, e in gaps[:2000]:
            best, best_key = "no host span (idle, or in untraced Python)", (0.0, 0.0)
            hi = bisect.bisect_left(starts, e)
            for thread, name, hs, he in host[max(0, hi - 400):hi]:
                over = min(e, he) - max(s, hs)
                if over <= 0:
                    continue
                key = (round(over / (e - s), 2), -(he - hs))
                if key > best_key:
                    best, best_key = f"{thread.split('/')[0]}/{name}", key
            by[best] += e - s
        return [[k, v] for k, v in by.most_common(n)]
