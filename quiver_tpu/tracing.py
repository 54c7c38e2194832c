"""Host-side span tracing: a lock-cheap ring buffer + Perfetto export.

``metrics.py`` (PR 5) answers "how much / how often" with counters and
histograms; this module answers "where did this request's 100 ms go?"
with a TIMELINE. It is the host-side half of the observability story —
the device half stays ``jax.profiler`` / XProf named scopes — and the
observation layer the ROADMAP item 4 controller reads: overlap problems
(cold-tier prefetch behind compute, coalesce wait vs dispatch) are
invisible in percentiles but obvious in a trace.

Design constraints, in order:

1. **Cheap when off.** The ring is opt-in (``QT_TRACE=1`` /
   ``QT_TRACE=/path/out.json`` / :func:`enable`); disabled, every hook
   is one attribute check (``record``). The per-BATCH stages (below)
   are timed whether it is on or off, since the server's counters read
   them: two clock reads and one ``TraceAnnotation`` each, about a
   microsecond, nine a batch. Nothing is timed per request.
2. **Lock-cheap when on.** Records land in a fixed-capacity ring
   buffer: one atomic ``next(itertools.count())`` for the slot, one
   list-item store for the record (both single bytecode effects under
   the GIL — no lock, no allocation beyond the record tuple). When the
   ring wraps, the oldest spans are overwritten: a long-running server
   keeps the RECENT window, bounded memory by construction
   (``scripts/check_leak.py`` phase 7 pins this).
3. **Never inside jit.** Spans time HOST work around device dispatches;
   nothing here touches a traced program, so the PR 5 invariants (zero
   per-step host syncs, bit-identical outputs with tracing on/off,
   donation intact) hold trivially — and are still pinned explicitly in
   ``tests/test_serving.py``.

**Stages** (:func:`stage`) are the spans of work that is happening
NOW on the serving path, one call site each and three readers: the
profiler (a ``jax.profiler.TraceAnnotation`` of the same name, on the
device trace's clock, when a profiler session is on), the caller (the
duration, always: ``MicroBatchServer`` sums them into
``snapshot()["serving"]``), and this ring (when enabled). :data:`STAGES`
lists them. Waits that are only known afterwards
(``serve.admission_wait``, ``serve.coalesce_wait``, ``serve.request``,
``pipeline.queue_wait``) cannot be annotations; they stay
:func:`record`-only.

A span record is ``(name, tid, t0, dur, trace_id, args)``: ``t0``/
``dur`` in ``time.perf_counter()`` seconds, ``tid`` the recording
thread, ``trace_id`` an optional correlation id (the serving layer
gives every request one and stamps each request span with the id of
the BATCH that carried it, so a request's admission -> coalesce ->
dispatch -> scatter path is one click-through in the viewer), ``args``
a small JSON-able dict.

:func:`export_chrome_trace` writes the Chrome trace-event JSON the
Perfetto UI (https://ui.perfetto.dev) and ``chrome://tracing`` load
directly: complete (``"ph": "X"``) events on named thread tracks, span
``args`` (including ``trace_id``) visible in the selection panel.

**Cross-process propagation** (the fleet plane's tracing leg): a span
timeline is per-process, but a REQUEST crosses processes — a client
submits, a serve replica answers. :func:`inject` stamps a compact
trace context (``trace_id``, optional parent span name, the sender's
replica label) into any dict-shaped request metadata; the receiving
side calls :func:`extract` and continues recording under the SAME
``trace_id`` (``serving.MicroBatchServer.submit(node_id, context=...)``
does this). Injected ids are *globally* unique — the pid rides the
high bits (:meth:`Tracer.new_global_trace_id`) so ids minted by
different clients/replicas never collide in a merged trace. Each
process exports with its own real ``pid`` plus a ``process_name``
metadata row (the replica label, ``QT_REPLICA`` / :func:`set_replica`
/ the ``replica=`` export arg), and :func:`merge_chrome_traces`
concatenates N exports into one file — Perfetto renders one process
track group per replica, and searching the injected ``trace_id``
lights up the request's spans across every process that touched it.

Usage::

    from quiver_tpu import tracing
    tracing.enable()
    with tracing.stage("stage.load", args={"rows": 4096}):
        ...
    tracing.export_chrome_trace("/tmp/trace.json")   # -> Perfetto

    # client process:
    meta = tracing.inject({})                  # -> request metadata
    # replica process (its spans carry meta's trace_id):
    ctx = tracing.extract(meta)
    with tracing.stage("serve.request", trace_id=ctx.trace_id):
        ...
"""

from __future__ import annotations

import atexit
import itertools
import json
import os
import threading
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

from jax.profiler import TraceAnnotation

Record = Tuple[str, int, float, float, Optional[int], Optional[dict]]

DEFAULT_CAPACITY = 65536

# the compact carrier keys inject()/extract() use inside request
# metadata — namespaced so they coexist with application fields
CTX_TRACE_ID = "qt.trace_id"
CTX_PARENT = "qt.parent"
CTX_REPLICA = "qt.replica"


class TraceContext(NamedTuple):
    """The propagated trace context: the correlation id a client
    minted, the span name it was under (informational), and the
    SENDER's replica label."""

    trace_id: int
    parent: Optional[str] = None
    replica: Optional[str] = None


# the process's replica label (fleet identity): QT_REPLICA env, or
# set_replica(); stamps outgoing contexts and the Perfetto export's
# process_name row
_replica: Optional[str] = os.environ.get("QT_REPLICA") or None


def set_replica(name: Optional[str]) -> None:
    """Set this process's replica label (overrides ``QT_REPLICA``)."""
    global _replica
    _replica = str(name) if name else None


def get_replica() -> Optional[str]:
    return _replica


class Tracer:
    """Fixed-capacity span ring buffer (see module doc for the
    concurrency argument). One process-wide instance normally suffices
    (:func:`get_tracer`); independent tracers compose for tests."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._ring: List[Optional[Record]] = [None] * self.capacity
        self._seq = itertools.count()
        self._ids = itertools.count(1)
        self._tid_names: Dict[int, str] = {}
        self._enabled = False
        # optional tail sampler (quiver_tpu.tailsampling.TailSampler):
        # every recorded span is ALSO offered to it — the always-on
        # keep/drop decision rides the same one recording path, one
        # attribute check when absent
        self._sampler = None

    # -- switch -------------------------------------------------------------
    @property
    def enabled(self) -> bool:
        return self._enabled

    def enable(self, capacity: Optional[int] = None) -> "Tracer":
        """Turn recording on (optionally resizing — a resize discards
        already-recorded spans)."""
        if capacity is not None and int(capacity) != self.capacity:
            if capacity < 1:
                raise ValueError(f"capacity must be >= 1, got {capacity}")
            self.capacity = int(capacity)
            self.clear()
        self._enabled = True
        return self

    def disable(self) -> "Tracer":
        self._enabled = False
        return self

    def clear(self) -> None:
        """Drop every recorded span (the ring survives, emptied)."""
        # swap ring and sequence together; record() indexes a LOCAL ref
        # of the ring by its own length, so a racing writer lands its
        # record in whichever ring it grabbed, never out of bounds. A
        # racing writer may register its thread name into the old dict
        # (lost) — its spans still export, just without the name row.
        self._ring = [None] * self.capacity
        self._seq = itertools.count()
        self._tid_names = {}

    # -- recording ----------------------------------------------------------
    def new_trace_id(self) -> int:
        """A fresh correlation id (process-unique, monotonic)."""
        return next(self._ids)

    def new_global_trace_id(self) -> int:
        """A fresh correlation id safe to PROPAGATE across processes:
        the pid rides the high bits above the local counter, so two
        replicas (or a client and a replica) can each mint ids and a
        merged fleet trace still has no collisions. Same int domain as
        :meth:`new_trace_id` — span records don't care which minted
        theirs."""
        return ((os.getpid() & 0x3FFFFF) << 24) | \
            (next(self._ids) & 0xFFFFFF)

    def record(self, name: str, t0: float, dur: float,
               trace_id: Optional[int] = None,
               args: Optional[dict] = None) -> None:
        """File one completed span from timestamps the caller already
        holds (``t0`` from ``time.perf_counter()``, ``dur`` seconds) —
        the zero-extra-clock-read form the hot paths use."""
        if not self._enabled:
            return
        tid = threading.get_ident()
        if tid not in self._tid_names:
            self._tid_names[tid] = threading.current_thread().name
        ring = self._ring
        ring[next(self._seq) % len(ring)] = (
            name, tid, t0, dur, trace_id, args)
        s = self._sampler
        if s is not None:
            s.offer(name, tid, t0, dur, trace_id, args)

    def set_sampler(self, sampler) -> None:
        """Attach (or, with ``None``, detach) a tail sampler — an
        object whose ``offer(name, tid, t0, dur, trace_id, args)`` is
        called for every recorded span. ``tailsampling.TailSampler``
        is the in-tree one; ``clear()`` leaves the attachment alone."""
        self._sampler = sampler

    def sampler(self):
        return self._sampler

    # -- reading / export ---------------------------------------------------
    def __len__(self) -> int:
        return sum(1 for r in self._ring if r is not None)

    def records(self) -> List[Record]:
        """Chronological snapshot of the retained spans (<= capacity;
        the ring keeps the most recent ones once wrapped)."""
        recs = [r for r in self._ring if r is not None]
        recs.sort(key=lambda r: r[2])
        return recs

    def export_chrome_trace(self, path: str,
                            replica: Optional[str] = None) -> int:
        """Write the retained spans as Chrome trace-event JSON (the
        format Perfetto / ``chrome://tracing`` load). Returns the number
        of span events written. Timestamps are ``perf_counter``-relative
        microseconds — offsets within the trace are what matter.

        Every event carries this process's real ``pid`` and the export
        leads with a ``process_name`` metadata row (``replica`` arg,
        else the process replica label, else ``pid <n>``) — so N
        replicas' exports merged into one file
        (:func:`merge_chrome_traces`) render one labeled process track
        group each instead of collapsing into anonymous processes."""
        pid = os.getpid()
        label = replica if replica is not None else _replica
        # copy before iterating: recorder threads (pipeline workers, a
        # live coalescer) may register a first-seen tid mid-export —
        # iterating the live dict would raise and lose the whole trace
        events: List[dict] = [
            {"ph": "M", "pid": pid, "tid": 0, "name": "process_name",
             "args": {"name": label or f"pid {pid}"}}]
        events += [
            {"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
             "args": {"name": tname}}
            for tid, tname in sorted(self._tid_names.copy().items())]
        recs = self.records()
        for name, tid, t0, dur, trace_id, args in recs:
            ev = {"ph": "X", "pid": pid, "tid": tid, "name": name,
                  "cat": name.split(".", 1)[0],
                  "ts": round(t0 * 1e6, 3),
                  "dur": round(max(dur, 0.0) * 1e6, 3)}
            a = dict(args) if args else {}
            if trace_id is not None:
                a["trace_id"] = trace_id
            if a:
                ev["args"] = a
            events.append(ev)
        with open(path, "w") as f:
            # default=str: span args may carry numpy scalars etc.; a
            # lossy string beats a failed export
            json.dump({"traceEvents": events, "displayTimeUnit": "ms"},
                      f, default=str)
        return len(recs)


# -- the process-default tracer ---------------------------------------------

_tracer = Tracer(int(os.environ.get("QT_TRACE_CAPACITY",
                                    str(DEFAULT_CAPACITY))))


def get_tracer() -> Tracer:
    """The process-default :class:`Tracer` every in-tree hook records
    into."""
    return _tracer


def enabled() -> bool:
    return _tracer._enabled


def enable(capacity: Optional[int] = None) -> Tracer:
    return _tracer.enable(capacity)


def disable() -> Tracer:
    return _tracer.disable()


def clear() -> None:
    _tracer.clear()


def new_trace_id() -> int:
    return _tracer.new_trace_id()


def new_global_trace_id() -> int:
    return _tracer.new_global_trace_id()


# -- stages: one call site, three readers -------------------------------------

# every span filed through stage(), per batch: the serving coalescer's
# two, the pipeline worker's two, and dispatch with its four children
STAGES = ("serve.batch_coalesce", "serve.pipe_submit", "pipeline.idle",
          "pipeline.execute", "serve.dispatch", "serve.put", "serve.launch",
          "serve.get", "serve.scatter")

_open_stage = threading.local()


class stage:
    """Context manager for a stage that is happening now.

    Entering opens a ``jax.profiler.TraceAnnotation(name)`` (an atomic
    check while no profiler session is on) and reads ``perf_counter``;
    leaving reads it again, keeps the duration on ``.dur`` for the
    caller, and, when the ring is enabled, files the record
    :func:`record` would. ``trace_id`` defaults to the enclosing
    stage's on this thread, so the children of a batch's
    ``serve.dispatch`` carry its batch id wherever they are written.
    ``args`` may be set on the object before it closes (a batch's fill
    is only known at the end).

        with tracing.stage("serve.get") as st:
            rows = np.asarray(jax.device_get(logits))
        get_s += st.dur
    """

    __slots__ = ("name", "trace_id", "args", "t0", "dur", "_ann", "_outer")

    def __init__(self, name: str, trace_id: Optional[int] = None,
                 args: Optional[dict] = None):
        self.name = name
        self.trace_id = trace_id
        self.args = args
        self.dur = 0.0

    def __enter__(self) -> "stage":
        self._outer = outer = getattr(_open_stage, "top", None)
        if self.trace_id is None and outer is not None:
            self.trace_id = outer.trace_id
        _open_stage.top = self
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.dur = time.perf_counter() - self.t0
        self._ann.__exit__(*exc)
        _open_stage.top = self._outer
        _tracer.record(self.name, self.t0, self.dur, self.trace_id,
                       self.args)


# -- cross-process propagation ------------------------------------------------


def inject(carrier: Optional[dict] = None,
           trace_id: Optional[int] = None,
           parent: Optional[str] = None,
           replica: Optional[str] = None) -> dict:
    """Stamp a compact trace context into ``carrier`` (request
    metadata — any JSON-able dict; created when ``None``) and return
    it. ``trace_id`` defaults to a fresh GLOBAL id
    (:func:`new_global_trace_id` — pid-prefixed, collision-free across
    a fleet); ``replica`` defaults to this process's label. The
    receiving process hands the carrier to :func:`extract` (or to
    ``MicroBatchServer.submit(node_id, context=carrier)``) and its
    spans continue under the same ``trace_id``."""
    if carrier is None:
        carrier = {}
    carrier[CTX_TRACE_ID] = int(trace_id) if trace_id is not None \
        else new_global_trace_id()
    if parent is not None:
        carrier[CTX_PARENT] = str(parent)
    label = replica if replica is not None else _replica
    if label is not None:
        carrier[CTX_REPLICA] = str(label)
    return carrier


def extract(carrier) -> Optional[TraceContext]:
    """Read a trace context out of request metadata. Tolerant by
    design: ``None``, a non-dict, a dict without the context keys, or
    a mangled id all return ``None`` — a request without a usable
    context is simply untraced, never an error."""
    if not isinstance(carrier, dict):
        return None
    raw = carrier.get(CTX_TRACE_ID)
    try:
        tid = int(raw)
    except (TypeError, ValueError):
        return None
    parent = carrier.get(CTX_PARENT)
    replica = carrier.get(CTX_REPLICA)
    return TraceContext(tid,
                        str(parent) if parent is not None else None,
                        str(replica) if replica is not None else None)


def merge_chrome_traces(paths: Sequence[str], out_path: str) -> int:
    """Merge N per-process Chrome trace exports into ONE file Perfetto
    loads whole — the fleet view: one process track group per replica
    (each export's ``process_name`` metadata row names it), request
    spans correlated across groups by the propagated ``trace_id``.
    Two exports claiming the same pid (pid reuse across hosts or
    restarts) are disambiguated by offsetting the later file's pids —
    labels and intra-file structure are preserved. Returns the total
    number of events written. Files that fail to parse are skipped (a
    half-written export from a dying replica must not lose the rest
    of the fleet's trace)."""
    events: List[dict] = []
    used_pids: set = set()
    for p in paths:
        try:
            with open(p) as f:
                doc = json.load(f)
            evs = doc["traceEvents"] if isinstance(doc, dict) else doc
            if not isinstance(evs, list):
                continue
        except (OSError, ValueError, KeyError):
            continue
        file_pids = {e.get("pid") for e in evs
                     if isinstance(e, dict) and "pid" in e}
        remap: Dict[int, int] = {}
        for fp in sorted(x for x in file_pids if isinstance(x, int)):
            np_ = fp
            while np_ in used_pids:
                np_ += 1 << 22          # above the pid namespace
            remap[fp] = np_
            used_pids.add(np_)
        for e in evs:
            if not isinstance(e, dict):
                continue
            e = dict(e)
            if isinstance(e.get("pid"), int):
                e["pid"] = remap.get(e["pid"], e["pid"])
            events.append(e)
    with open(out_path, "w") as f:
        json.dump({"traceEvents": events, "displayTimeUnit": "ms"},
                  f, default=str)
    return len(events)


def record(name: str, t0: float, dur: float,
           trace_id: Optional[int] = None,
           args: Optional[dict] = None) -> None:
    _tracer.record(name, t0, dur, trace_id, args)


def records() -> List[Record]:
    return _tracer.records()


def export_chrome_trace(path: str, replica: Optional[str] = None) -> int:
    return _tracer.export_chrome_trace(path, replica=replica)


# QT_TRACE=1 turns recording on; QT_TRACE=<path> additionally exports
# the ring to <path> at interpreter exit (the no-code-changes workflow:
# QT_TRACE=/tmp/trace.json python examples/serve_sage.py)
_env = os.environ.get("QT_TRACE", "")
if _env and _env.lower() not in ("0", "false", "no", "off"):
    _tracer.enable()
    if _env.lower() not in ("1", "true", "yes", "on"):
        atexit.register(_tracer.export_chrome_trace, _env)
