"""``idle_in_stage``: the device's idle time by what the host was doing,
in ms per ``per``.

Over chip 0: the time inside the window in which NO op runs on the device
(the complement of ``Trace.busy``) AND a host span named ``stage`` is open
(the union of the ``Trace.host`` events of that name, on any thread: two
that overlap count once). The stages are ``quiver_tpu.tracing.stage``'s
``TraceAnnotation``s, on the device trace's clock. The serve worker's four
(``serve.put``, ``serve.launch``, ``serve.get``, ``serve.scatter``) follow
one another on one thread, so their readings partition the idle time that
falls inside ``serve.dispatch``; what the coalescer's thread has open at
the time decides nothing (``Trace.idle_gaps`` names each gap by the ONE
span that overlaps it most, which is the coalescer's whatever the worker
does).

    {"reducer": "idle_in_stage", "args": {"stage": "serve.get",
                                         "per": "batches"}}

A device that is busy all through the stage's spans reads 0. No span of
that name inside the window (the program has no such stage, or no host
plane was traced) or no device plane is None: the metric is left out of
the line.
"""

from chipbench import readers, spec, trace


def read(ctx, stage):
    """Seconds of chip 0's idle time under the spans named ``stage``,
    None where the window holds no such span."""
    t = ctx["trace"]
    spans = trace._union([(max(s, t.t0), min(e, t.t1))
                          for _, name, s, e in t.host
                          if name == stage and e > t.t0 and s < t.t1])
    if not spans or not t.devices:
        return None
    wait = spec.plugin("reducers", "cold_wait")
    return max(wait._length(spans)
               - wait._common(spans, t.busy(t.devices[0])), 0.0)


def reduce(ctx, stage, per=None):
    found = read(ctx, stage)
    n = readers.count_of(ctx, per)
    return None if found is None or n is None else 1e3 * found / n
