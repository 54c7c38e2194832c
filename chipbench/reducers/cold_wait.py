"""``cold_wait``: the time a step's device does nothing but wait for the
host tier, in ms per ``per``.

A row out of the pinned-host tier is a transfer the device starts and then
waits for: in the compiled step a pair of ops under ``pattern``'s scope,
``<name>-start`` and ``<name>-done`` (``dynamic-slice-start`` /
``-done`` of the host buffer, one pair a row; a host call, ``HostExecute``,
would be such a pair too). Over chip 0: the time covered by the spans from
a start's beginning to its done's end, less the time in which any OTHER op
runs on the device (an op that only holds others, the loop around the
fetches, is not one: the loop's own turns between two ops count as
waiting). Whether the profiler shows the wait inside a long
``-done`` or as a gap in the device's line, it is counted here, once, and
pairs that overlap (several rows in flight) are counted once too.

    {"reducer": "cold_wait", "args": {"pattern": "qt_lookup_cold",
                                     "per": "steps"}}

No such pair in the trace (no tiered store under the step, or no device
trace) is None: the metric is left out of the line.
"""

import re

from chipbench import readers, trace

ASYNC = re.compile(r"^(.+?)-(start|done)(\.\d+)?$")


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _common(a, b) -> float:
    """Time covered by both of two lists of disjoint sorted intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def read(ctx, pattern):
    """``(waited, idle)`` seconds of chip 0 inside the start/done spans
    under ``pattern``: ``waited`` with no OTHER op running, ``idle`` with
    no op at all (a gap in the device's line); None where the trace holds
    no such pair. An op that holds other ops (the ``while`` around the
    fetches, a ``cond``) is no op of its own for ``waited``: only what
    runs inside it counts."""
    rx = re.compile(pattern)
    devices = ctx["trace"].devices
    ops = sorted(devices[0], key=lambda o: (o.start, -o.end)) \
        if devices else []
    holds = set()
    stack = []
    for o in ops:
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack:
            holds.add(id(stack[-1]))
        stack.append(o)
    open_at, spans, others = {}, [], []
    for o in ops:
        m = ASYNC.match(o.name) if rx.search(o.scope) else None
        if m and m.group(2) == "start":
            open_at[m.group(1), m.group(3)] = o.start
        elif m and (m.group(1), m.group(3)) in open_at:
            spans.append((open_at.pop((m.group(1), m.group(3))), o.end))
        elif not m and id(o) not in holds:
            others.append((o.start, o.end))
    if not spans:
        return None
    spans = trace._union(spans)
    covered = _length(spans)
    busy = trace._union([(o.start, o.end) for o in ops])
    return (max(covered - _common(spans, trace._union(others)), 0.0),
            max(covered - _common(spans, busy), 0.0))


def reduce(ctx, pattern, per=None):
    found = read(ctx, pattern)
    n = readers.count_of(ctx, per)
    return None if found is None or n is None else 1e3 * found[0] / n
