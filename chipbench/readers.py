"""Per-layer metrics. Each metric is a file ``layer_metrics/<name>.json``
that names a reducer and its arguments; a metric that reads an existing
scope, counter or host series is added as a file, with no code. A reducer
is one of ``REDUCERS`` below or, found by name, ``reducers/<name>.py``'s
``reduce(ctx, **args)``. A count of work (bytes, FLOPs) comes from the
cell's shapes through ``work/<name>.py``'s ``work(cell, **args)``, never
from the operations the program happens to use.

A reducer gets the traced run (``ctx``) and returns a number, or None
where it finds nothing to read: the harness then leaves the metric out
of the line. None of them returns 0 for a share of a roofline or a peak.

``ctx``: ``trace`` (``trace.Trace``), ``facts`` (what the run counted and
timed on the host: ``steps``, ``batches``, ``enqueue_s`` ...),
``counters`` (the server's ``snapshot()["serving"]``), ``cell``, ``peaks``,
``chips``.
"""

from __future__ import annotations

import re
import statistics

from . import flops, spec


def _fill(template: str, cell) -> str:
    """``{nodes}``, ``{dim}``, ``{frontier}`` ... from the cell's shapes."""
    cfg = cell.config
    caps = flops.frontier_caps(cell.batch, cfg["fanout"])
    return template.format(nodes=cfg["nodes"], dim=cfg["feature_dim"],
                           frontier=caps[-1], batch=cell.batch)


def work_of(cell, name: str, **args) -> dict:
    """``{"bytes": ..., "flops": ...}`` (either or both) of one execution,
    from ``work/<name>.py``."""
    return spec.plugin("work", name).work(cell, **args)


def count_of(ctx, per):
    if per is None:
        return 1.0
    n = ctx["facts"].get(per)
    return float(n) if n else None


def scope_ms(ctx, pattern, per=None):
    """Device self time of the ops whose scope matches ``pattern``, in ms,
    divided by the run's count ``per`` (``steps``, ``batches``)."""
    rx = re.compile(pattern)
    s = ctx["trace"].seconds(lambda o: rx.search(o.scope) is not None)
    n = count_of(ctx, per)
    return None if s is None or n is None else 1e3 * s / n


def scope_share(ctx, pattern):
    """The same time as a share of the device's busy time, in %."""
    rx = re.compile(pattern)
    s = ctx["trace"].seconds(lambda o: rx.search(o.scope) is not None)
    busy = ctx["trace"].busy_s
    return None if s is None or busy <= 0 else 100.0 * s / busy


def opcode_ms(ctx, pattern, per=None):
    """Device self time of the ops whose instruction text matches
    ``pattern`` (an opcode such as ``all-reduce``), in ms per ``per``."""
    rx = re.compile(pattern)
    s = ctx["trace"].seconds(lambda o: rx.search(o.text) is not None)
    n = count_of(ctx, per)
    return None if s is None or n is None else 1e3 * s / n


def device_idle(ctx):
    t = ctx["trace"]
    if t.window_s <= 0 or t.busy_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def gather_roofline(ctx, operand, result, per):
    """The frontier gather, found by what it reads and writes: the ops
    whose instruction text has ``operand`` among its operands and
    ``result`` as its result (both templates over the cell's shapes).
    Bytes are ``flops.gather_bytes`` an execution, the bound is HBM
    bandwidth; the share is the least time over the measured one."""
    cell = ctx["cell"]
    operand, result = _fill(operand, cell), _fill(result, cell)

    def pick(o):
        head, _, args = o.text.partition("(")
        return head.split(" = ", 1)[-1].startswith(result) and operand in args

    s = ctx["trace"].seconds(pick)
    n = count_of(ctx, per)
    if s is None or n is None or s <= 0 or not ctx.get("peaks"):
        return None
    least = work_of(cell, "frontier_gather")["bytes"] \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (s / n)


def step_mfu(ctx, per, train):
    """The whole step's share of the chips' peak: the model's FLOPs at
    the cell's shapes (the work function the configuration names under
    ``step_flops``; absent: the SAGE layers' matmuls), times the
    executions in the traced window, over window x chips x peak."""
    cell = ctx["cell"]
    n = count_of(ctx, per)
    t = ctx["trace"]
    if n is None or t.window_s <= 0 or not ctx.get("peaks"):
        return None
    per_exec = work_of(cell, cell.named("step_flops"),
                       train=bool(train))["flops"]
    # a data-parallel step runs one such batch on every chip
    work = per_exec * n * ctx["chips"]
    return 100.0 * work / (t.window_s * ctx["chips"]
                           * ctx["peaks"]["bf16_flops_per_s"])


def host_stat(ctx, series, stat, scale=1.0):
    """A statistic of a series the harness timed on the host clock:
    ``median``, ``p95`` or ``mean`` of ``facts[series]``, times ``scale``."""
    xs = ctx["facts"].get(series)
    if not xs:
        return None
    xs = sorted(xs)
    if stat == "median":
        v = statistics.median(xs)
    elif stat == "mean":
        v = statistics.fmean(xs)
    elif stat == "p95":
        v = xs[min(len(xs) - 1, int(0.95 * len(xs)))]
    else:
        raise ValueError(f"unknown statistic {stat!r}")
    return scale * v


def counter_ratio(ctx, counter, over, scale=100.0):
    """A counter of the server's snapshot over a number of the cell's
    (``server.batch_cap``) or over another counter."""
    c = ctx.get("counters") or {}
    if counter not in c:
        return None
    den = c.get(over)
    if den is None:
        den = ctx["cell"].cell.get("server", {}).get(over)
    return scale * c[counter] / den if den else None


REDUCERS = {f.__name__: f for f in (
    scope_ms, scope_share, opcode_ms, device_idle, gather_roofline, step_mfu,
    host_stat, counter_ratio)}


def reducer(name: str):
    """``REDUCERS[name]``, else ``reducers/<name>.py``'s ``reduce``."""
    return REDUCERS.get(name) or spec.plugin("reducers", name).reduce


def read_all(ctx) -> dict:
    """Every per-layer metric of the cell that finds something to read."""
    out = {}
    for m in ctx["cell"].per_layer:
        value = reducer(m["reducer"])(ctx, **m.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
