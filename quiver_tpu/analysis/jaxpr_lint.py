"""Static jaxpr verifier — the repo's performance contract as rules.

The invariants that make the bandwidth-tiered gather + latency-hidden
sampling story hold on TPU (zero per-step host syncs, donated train
states, shard-uniform collective branching, dedup-bounded cold reads,
narrow exchange payloads, a flat executable cache) used to be enforced
by a patchwork: jaxpr walkers in ``tests/_traffic.py``, runtime phases
in ``scripts/check_leak.py``, greps in ``scripts/lint.sh``. This module
absorbs the walkers and generalizes them into a declarative rule
registry over *entry points* (an :class:`EntrySpec`: a traceable
callable + example args + the invariants it promises). Each rule walks
the TRACED program once — no compile, no timing, CPU-friendly — and
returns :class:`~quiver_tpu.analysis.findings.Finding` records.

Rules
-----
``no_host_sync``           no callback/infeed/outfeed equation anywhere
                           in the traced program (incl. ``pure_callback``
                           / ``io_callback`` / ``debug_callback`` — a
                           stray ``jax.debug.print`` in a metered step
                           is a per-step host round trip).
``donation_honored``       every ``donate_argnums`` buffer's (shape,
                           dtype) reappears among the outputs — drift
                           means XLA silently COPIES instead of reusing
                           the donated buffer (same class
                           ``_check_donatable`` guards at runtime, but
                           checked on the one shared trace).
``collective_divergence``  no collective (``all_to_all``/``psum``/
                           ``ppermute``/...) inside a ``lax.cond``
                           branch whose predicate is not uniform across
                           the mesh axis (not derived from a ``pmax``/
                           ``psum`` reduction) — divergent shards would
                           DEADLOCK the collective (PR 4's bug class).
``traffic_budget``         gathers on a declared tier's storage read at
                           most the declared row budget on the
                           unconditional path; compact-exchange
                           collectives ship at most the declared
                           fraction of the dense payload, and
                           dense-shaped payloads appear only inside
                           fallback (``lax.cond``) branches.
``executable_census``      the reachable jit-program set per entry
                           point, enumerated from declared DISCRETE
                           knob lattices, is finite and within a
                           declared cardinality — the static
                           precondition for cheap re-jit actuation
                           (ROADMAP item 4) and the flat-cache pins in
                           ``check_leak``.

The four walkers (``gather_reads``, ``tier_read_bytes``,
``host_sync_eqns``, ``collective_payloads``) keep their historical
signatures — ``tests/_traffic.py`` re-exports them so the existing
traffic pins run against THIS implementation and cannot drift.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import numpy as np

import jax

from .findings import ERROR, INFO, Finding

try:
    _Literal = jax.core.Literal
except AttributeError:      # pragma: no cover - jax moved core
    from jax._src.core import Literal as _Literal


# host round-trip primitives: the structural definition of "this traced
# program syncs with the host" — callback-based syncs included
# (jax.debug.print traces to debug_print, jax.debug.callback to
# debug_callback; jax.pure_callback / io_callback are the blocking data
# paths)
HOST_SYNC_PRIMS = ("io_callback", "pure_callback", "debug_callback",
                   "debug_print", "python_callback", "infeed", "outfeed")

# collectives that rendezvous across the mesh axis — any of these inside
# a divergent cond branch deadlocks the mesh
COLLECTIVE_PRIMS = ("all_to_all", "psum", "pmax", "pmin", "ppermute",
                    "all_gather", "reduce_scatter", "pgather")

# reductions whose output is, by construction, UNIFORM across the axis
MESH_REDUCE_PRIMS = ("psum", "pmax", "pmin")


# ---------------------------------------------------------------------------
# the walkers (absorbed from tests/_traffic.py — signatures preserved)
# ---------------------------------------------------------------------------


def _sub_jaxprs(eqn):
    """Every inner jaxpr a primitive's params carry (pjit/closed calls,
    shard_map's open jaxpr, scan bodies) EXCEPT cond branches — the
    walkers treat those specially to track fallback depth."""
    for name, sub in eqn.params.items():
        if eqn.primitive.name == "cond" and name == "branches":
            continue
        vals = sub if isinstance(sub, (tuple, list)) else (sub,)
        for v in vals:
            if hasattr(v, "jaxpr"):
                yield v.jaxpr
            elif hasattr(v, "eqns"):
                yield v


def _as_jaxpr(obj):
    """ClosedJaxpr | Jaxpr -> the open Jaxpr."""
    return obj.jaxpr if hasattr(obj, "jaxpr") else obj


def gather_reads(jaxpr, src_shape, dtype=None):
    """Gather equations reading an operand of ``src_shape`` (and
    optionally ``dtype``) anywhere in ``jaxpr`` (a ClosedJaxpr or inner
    jaxpr). Returns ``[(out_rows, cond_depth)]`` — ``cond_depth`` 0 for
    reads on the unconditional path, +1 per enclosing ``lax.cond``
    branch (fallback paths)."""
    jxp = _as_jaxpr(jaxpr)

    def walk(j, depth):
        out = []
        for eqn in j.eqns:
            if eqn.primitive.name == "cond":
                for br in eqn.params["branches"]:
                    out += walk(br.jaxpr, depth + 1)
            elif eqn.primitive.name == "gather":
                aval = eqn.invars[0].aval
                if tuple(aval.shape) == tuple(src_shape) and \
                        (dtype is None or aval.dtype == dtype):
                    out.append((eqn.outvars[0].aval.shape[0], depth))
            for sub in _sub_jaxprs(eqn):
                out += walk(sub, depth)
        return out

    return walk(jxp, 0)


def tier_read_bytes(fn, args, tier, max_depth=0):
    """Total bytes ``fn(*args)``'s traced program gathers from
    ``tier``'s storage at cond depth <= ``max_depth`` (default: only
    the always-taken narrow path). ``tier`` is a plain array or a
    quantized-tier pytree — sidecar reads count toward the total, so
    the byte comparison against an fp32 tier is honest."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    # distinct (shape, dtype) specs, ONCE each: a quantized tier's
    # scale and zero share a spec, and counting per leaf would tally
    # each matching gather equation twice
    total = 0
    for shape, dt in _tier_specs(tier):
        width = int(np.prod(shape[1:])) * dt.itemsize
        for rows, depth in gather_reads(jaxpr, shape, dt):
            if depth <= max_depth:
                total += rows * width
    return total


def _tier_specs(tier):
    """Distinct (shape, dtype) storage specs of a tier pytree."""
    return {(tuple(leaf.shape), jax.numpy.dtype(leaf.dtype))
            for leaf in jax.tree_util.tree_leaves(tier)}


def host_sync_eqns(fn, args, prims=HOST_SYNC_PRIMS):
    """Every host-round-trip equation in the traced program — the
    structural pin that a jitted path performs ZERO per-step host
    syncs (the metrics counters must ride out as a plain device
    output, never via a callback). Returns ``[primitive_name]``;
    assert it is empty."""
    return host_sync_eqns_jaxpr(jax.make_jaxpr(fn)(*args), prims)


def host_sync_eqns_jaxpr(jaxpr, prims=HOST_SYNC_PRIMS):
    """:func:`host_sync_eqns` on an already-traced jaxpr."""
    def walk(j):
        out = []
        for eqn in j.eqns:
            if eqn.primitive.name in prims:
                out.append(eqn.primitive.name)
            if eqn.primitive.name == "cond":
                for br in eqn.params["branches"]:
                    out += walk(br.jaxpr)
            for sub in _sub_jaxprs(eqn):
                out += walk(sub)
        return out

    return walk(_as_jaxpr(jaxpr))


def collective_payloads(fn, args, prims=("all_to_all",),
                        with_depth=False):
    """Every collective equation's payload in the traced program —
    the exchange's wire traffic. Returns ``[(shape, dtype, bytes)]``
    (requests AND responses both appear; callers filter by shape/dtype
    when they want one direction). ``with_depth=True`` appends the
    ``lax.cond`` nesting depth as a fourth element (0 = the
    unconditional path; the compact exchange keeps BOTH its narrow
    collectives and the dense fallback inside one cond, so callers
    separate them by payload shape, and use depth to assert nothing
    dense-shaped leaked onto the unconditional path)."""
    return collective_payloads_jaxpr(jax.make_jaxpr(fn)(*args), prims,
                                     with_depth)


def collective_payloads_jaxpr(jaxpr, prims=("all_to_all",),
                              with_depth=False):
    """:func:`collective_payloads` on an already-traced jaxpr."""
    def walk(j, depth):
        out = []
        for eqn in j.eqns:
            if eqn.primitive.name in prims:
                aval = eqn.invars[0].aval
                rec = (tuple(aval.shape),
                       jax.numpy.dtype(aval.dtype),
                       int(np.prod(aval.shape)) * aval.dtype.itemsize)
                out.append(rec + (depth,) if with_depth else rec)
            if eqn.primitive.name == "cond":
                for br in eqn.params["branches"]:
                    out += walk(br.jaxpr, depth + 1)
            for sub in _sub_jaxprs(eqn):
                out += walk(sub, depth)
        return out

    return walk(_as_jaxpr(jaxpr), 0)


# ---------------------------------------------------------------------------
# mesh-uniformity dataflow (the collective_divergence rule's engine)
# ---------------------------------------------------------------------------


class _DivergenceWalk:
    """Track which values are UNIFORM across the mesh axis through the
    program, and flag every ``lax.cond`` that (a) contains a collective
    in a branch and (b) branches on a non-uniform predicate.

    Uniform sources: literals, closed-over constants, replicated
    ``shard_map`` inputs, and the outputs of ``psum``/``pmax``/``pmin``
    (a reduction OVER the axis is the same on every shard). Non-uniform
    sources: sharded ``shard_map`` inputs and ``axis_index``. Everything
    else propagates: an op's output is uniform iff every input is —
    ``local_flag & pmax_flag`` is still divergent, which is exactly the
    bug class this exists to catch."""

    def __init__(self):
        self.divergent = []     # (prims_in_branches, depth, source)
        self._flagged = set()   # cond eqn ids already reported (loop
        #                         bodies are re-walked to fix-point)

    # -- helpers -----------------------------------------------------------

    @staticmethod
    def _in_u(uniform, atom):
        if isinstance(atom, _Literal):
            return True
        return uniform.get(atom, True)

    def _bind(self, jaxpr, in_uniform):
        jxp = _as_jaxpr(jaxpr)
        uniform = {v: True for v in jxp.constvars}
        for v, u in zip(jxp.invars, in_uniform):
            uniform[v] = bool(u)
        return jxp, uniform

    # -- the walk ----------------------------------------------------------

    def walk(self, jaxpr, in_uniform, depth=0, in_mesh=False):
        """Returns ``(out_uniform, collectives)`` where ``collectives``
        is every ``(prim, depth)`` rendezvous reachable in this scope."""
        jxp, uniform = self._bind(jaxpr, in_uniform)
        collectives = []
        for eqn in jxp.eqns:
            name = eqn.primitive.name
            ins = [self._in_u(uniform, a) for a in eqn.invars]
            outs_u = all(ins)

            if name == "shard_map":
                body = eqn.params["jaxpr"]
                in_names = eqn.params.get("in_names") or ()
                body_in = [len(n) == 0 for n in in_names] \
                    if in_names else [False] * len(eqn.invars)
                _, sub_coll = self.walk(body, body_in, depth,
                                        in_mesh=True)
                collectives += sub_coll
                outs_u = True       # back outside the mesh

            elif name == "cond":
                pred_u = ins[0]
                br_outs, br_coll = [], []
                for br in eqn.params["branches"]:
                    o, c = self.walk(br, ins[1:], depth + 1, in_mesh)
                    br_outs.append(o)
                    br_coll += c
                if in_mesh and br_coll and not pred_u and \
                        id(eqn) not in self._flagged:
                    self._flagged.add(id(eqn))
                    self.divergent.append(
                        (sorted({p for p, _ in br_coll}), depth,
                         eqn.source_info))
                collectives += br_coll
                outs_u = None       # per-output below
                for i, v in enumerate(eqn.outvars):
                    uniform[v] = pred_u and all(
                        o[i] if i < len(o) else False for o in br_outs)

            elif name in MESH_REDUCE_PRIMS:
                if in_mesh:
                    collectives.append((name, depth))
                outs_u = True if in_mesh else all(ins)

            elif name in COLLECTIVE_PRIMS:
                if in_mesh:
                    collectives.append((name, depth))
                outs_u = False

            elif name == "axis_index":
                outs_u = not in_mesh

            elif name == "while":
                cc = eqn.params["cond_nconsts"]
                bc = eqn.params["body_nconsts"]
                carry = ins[cc + bc:]
                # iterate to a TRUE fix-point: one body pass only
                # narrows the carry one hop, and a rotation chain of
                # length k launders axis-dependence through k carries —
                # the lattice only descends, so this terminates within
                # len(carry) passes
                while True:
                    body_out, c = self.walk(
                        eqn.params["body_jaxpr"], ins[cc:cc + bc] + carry,
                        depth, in_mesh)
                    collectives += c
                    new_carry = [a and b
                                 for a, b in zip(carry, body_out)]
                    if new_carry == carry:
                        break
                    carry = new_carry
                _, c = self.walk(eqn.params["cond_jaxpr"],
                                 ins[:cc] + carry, depth, in_mesh)
                collectives += c
                outs_u = None
                for v, u in zip(eqn.outvars, carry):
                    uniform[v] = u

            elif name == "scan":
                nc = eqn.params["num_consts"]
                ncar = eqn.params["num_carry"]
                carry = ins[nc:nc + ncar]
                while True:
                    body_out, c = self.walk(
                        eqn.params["jaxpr"],
                        ins[:nc] + carry + ins[nc + ncar:], depth,
                        in_mesh)
                    collectives += c
                    new_carry = [a and b
                                 for a, b in zip(carry, body_out[:ncar])]
                    if new_carry == carry:
                        break
                    carry = new_carry
                outs_u = None
                for i, v in enumerate(eqn.outvars):
                    uniform[v] = carry[i] if i < ncar else \
                        (body_out[i] if i < len(body_out) else False)

            else:
                inner = None
                for k in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                    cand = eqn.params.get(k)
                    if cand is not None and (hasattr(cand, "jaxpr")
                                             or hasattr(cand, "eqns")):
                        inner = cand
                        break
                if inner is not None:
                    n_in = len(_as_jaxpr(inner).invars)
                    sub_in = ins if n_in == len(ins) \
                        else [all(ins)] * n_in
                    sub_out, c = self.walk(inner, sub_in, depth, in_mesh)
                    collectives += c
                    outs_u = None
                    for i, v in enumerate(eqn.outvars):
                        uniform[v] = sub_out[i] if i < len(sub_out) \
                            else all(ins)
                else:
                    # walk any other nested jaxprs conservatively (their
                    # conds still get checked; mapping is approximate)
                    for sub in _sub_jaxprs(eqn):
                        n_in = len(_as_jaxpr(sub).invars)
                        _, c = self.walk(sub, [all(ins)] * n_in, depth,
                                         in_mesh)
                        collectives += c

            if outs_u is not None:
                for v in eqn.outvars:
                    uniform[v] = outs_u
        return [self._in_u(uniform, v) for v in jxp.outvars], collectives


def divergent_cond_collectives(jaxpr):
    """Every ``lax.cond`` with collectives in a branch and a predicate
    that is NOT uniform across the mesh axis. Returns
    ``[(collective_prims, cond_depth, source_info)]`` — assert empty."""
    w = _DivergenceWalk()
    jxp = _as_jaxpr(jaxpr)
    w.walk(jxp, [True] * len(jxp.invars))
    return w.divergent


# ---------------------------------------------------------------------------
# entry points + the rule registry
# ---------------------------------------------------------------------------


@dataclass
class CensusSpec:
    """The declared reachable-executable lattice of one entry point.

    ``axes`` maps a knob name to its DISCRETE value lattice (any finite
    sequence) or directly to an int cardinality. A ``None`` (or any
    non-enumerable) axis means the knob is unbounded — the census rule
    ERRORs, because an unbounded knob means an unbounded executable
    cache and re-jit actuation is unsafe. The reachable program count
    is the product of the axis cardinalities and must not exceed
    ``max_programs``."""

    axes: Dict[str, Any]
    max_programs: int

    def axis_count(self, value) -> Optional[int]:
        if value is None or isinstance(value, bool):
            return None
        if isinstance(value, (str, bytes)):
            # a bare string is a typo'd one-element tuple, not a
            # lattice of its characters — refuse rather than miscount
            return None
        if isinstance(value, int):
            return value if value > 0 else None
        try:
            n = len(value)
        except TypeError:
            return None
        return n if n > 0 else None

    def count(self) -> Optional[int]:
        """Reachable program count, or None if any axis is unbounded."""
        total = 1
        for v in self.axes.values():
            n = self.axis_count(v)
            if n is None:
                return None
            total *= n
        return total


@dataclass
class EntrySpec:
    """One registered jitted hot path + the invariants it promises.

    ``fn``/``args`` give the single shared trace every rule walks.
    ``tier_budgets`` is a tuple of ``(tier, max_rows, max_depth)``: no
    gather on the tier's storage may read more than ``max_rows`` rows
    at cond depth <= ``max_depth``. ``exchange`` bounds collective
    payloads: ``{"prims": (...), "dense_bytes": int, "max_frac": f,
    "dense_shapes": (shape, ...)}``. ``rules=None`` runs every
    applicable rule."""

    name: str
    fn: Callable
    args: Tuple = ()
    donate_argnums: Tuple[int, ...] = ()
    sync_free: bool = True
    tier_budgets: Tuple = ()
    exchange: Optional[Dict] = None
    census: Optional[CensusSpec] = None
    rules: Optional[Sequence[str]] = None
    detail: Dict = field(default_factory=dict)
    _jaxpr: Any = field(default=None, repr=False)

    def jaxpr(self):
        """The one shared trace (cached — every rule walks this)."""
        if self._jaxpr is None:
            self._jaxpr = jax.make_jaxpr(self.fn)(*self.args)
        return self._jaxpr


def rule_no_host_sync(spec: EntrySpec):
    if not spec.sync_free:
        return []
    syncs = host_sync_eqns_jaxpr(spec.jaxpr())
    if not syncs:
        return []
    by_prim: Dict[str, int] = {}
    for p in syncs:
        by_prim[p] = by_prim.get(p, 0) + 1
    return [Finding(
        "no_host_sync", ERROR, spec.name,
        f"traced program performs {len(syncs)} host round trip(s): "
        + ", ".join(f"{p} x{n}" for p, n in sorted(by_prim.items()))
        + " — counters/telemetry must ride out as device outputs",
        {"primitives": by_prim})]


def rule_donation_honored(spec: EntrySpec):
    if not spec.donate_argnums:
        return []
    jaxpr = spec.jaxpr()
    spans, at = [], 0
    for a in spec.args:
        n = len(jax.tree_util.tree_leaves(a))
        spans.append((at, at + n))
        at += n
    out_pool: Dict[Tuple, int] = {}
    for aval in jaxpr.out_avals:
        k = (tuple(aval.shape), str(aval.dtype))
        out_pool[k] = out_pool.get(k, 0) + 1
    unmatched = []
    for argnum in spec.donate_argnums:
        lo, hi = spans[argnum]
        for aval in jaxpr.in_avals[lo:hi]:
            k = (tuple(aval.shape), str(aval.dtype))
            if out_pool.get(k, 0) > 0:
                out_pool[k] -= 1
            else:
                unmatched.append({"argnum": argnum, "shape": list(k[0]),
                                  "dtype": k[1]})
    if not unmatched:
        return []
    head = ", ".join(f"arg {u['argnum']}: {tuple(u['shape'])} "
                     f"{u['dtype']}" for u in unmatched[:4])
    return [Finding(
        "donation_honored", ERROR, spec.name,
        f"{len(unmatched)} donated buffer(s) have no same-shape/dtype "
        f"output to reuse ({head}) — XLA will silently copy instead of "
        "donating; fix the step to be shape/dtype-stable or drop "
        "donate_argnums",
        {"unmatched": unmatched})]


def rule_collective_divergence(spec: EntrySpec):
    out = []
    for prims, depth, src in divergent_cond_collectives(spec.jaxpr()):
        out.append(Finding(
            "collective_divergence", ERROR, spec.name,
            f"collective(s) {'/'.join(prims)} inside a lax.cond branch "
            f"(depth {depth}) whose predicate is NOT uniform across the "
            "mesh axis — shards can take different branches and "
            "deadlock the collective; pmax/psum-reduce the predicate "
            "over the axis first",
            {"collectives": list(prims), "cond_depth": depth}))
    return out


def rule_traffic_budget(spec: EntrySpec):
    out = []
    jaxpr = spec.jaxpr()
    for tier, max_rows, max_depth in spec.tier_budgets:
        for shape, dt in _tier_specs(tier):
            # SUMMED rows per storage component (each quantized-tier
            # leaf spec is checked separately — its sidecar gathers
            # mirror the data rows and must not double-count): a
            # regression that splits one budget-sized gather into two
            # still doubles tier traffic and must still flag
            reads = [r for r, d in gather_reads(jaxpr, shape, dt)
                     if d <= max_depth]
            total = sum(reads)
            if total > max_rows:
                out.append(Finding(
                    "traffic_budget", ERROR, spec.name,
                    f"gathers read {total} rows total "
                    f"({len(reads)} gather(s)) from the {shape} {dt} "
                    f"tier at cond depth <= {max_depth} — budget is "
                    f"{max_rows} rows (dedup/compaction bound "
                    "violated)",
                    {"rows": int(total), "budget": int(max_rows),
                     "tier_shape": list(shape),
                     "gathers": len(reads)}))
    ex = spec.exchange
    if ex:
        prims = tuple(ex.get("prims", ("all_to_all",)))
        payloads = collective_payloads_jaxpr(jaxpr, prims,
                                             with_depth=True)
        dense_shapes = {tuple(s) for s in ex.get("dense_shapes", ())}
        for shape, dt, nbytes, depth in payloads:
            if shape in dense_shapes and depth == 0:
                out.append(Finding(
                    "traffic_budget", ERROR, spec.name,
                    f"dense-shaped collective payload {shape} {dt} on "
                    "the UNCONDITIONAL path — dense exchange must live "
                    "only inside the lax.cond fallback",
                    {"shape": list(shape), "bytes": nbytes}))
        dense_bytes = ex.get("dense_bytes")
        max_frac = ex.get("max_frac", 0.25)
        if dense_bytes:
            # narrow payloads are separated by SHAPE, not depth: the
            # compact exchange keeps its narrow collectives INSIDE the
            # lax.cond (beside the dense fallback), so a depth filter
            # would sum to zero and never fire
            narrow = sum(b for s, _, b, _ in payloads
                         if tuple(s) not in dense_shapes)
            if narrow > max_frac * dense_bytes:
                out.append(Finding(
                    "traffic_budget", ERROR, spec.name,
                    f"compact-exchange payload is {narrow} bytes > "
                    f"{max_frac:.2f} x dense ({dense_bytes} bytes) — "
                    "the exchange is no longer narrow (cap "
                    "oversized?)",
                    {"narrow_bytes": int(narrow),
                     "dense_bytes": int(dense_bytes),
                     "max_frac": max_frac}))
    return out


def rule_executable_census(spec: EntrySpec):
    c = spec.census
    if c is None:
        return []
    out = []
    unbounded = [k for k, v in c.axes.items()
                 if c.axis_count(v) is None]
    if unbounded:
        return [Finding(
            "executable_census", ERROR, spec.name,
            f"knob axis/axes {', '.join(sorted(unbounded))} are "
            "UNBOUNDED — the reachable jit-program set cannot be "
            "enumerated, so the executable cache is not provably flat "
            "and re-jit actuation is unsafe; declare a finite discrete "
            "lattice",
            {"unbounded_axes": sorted(unbounded)})]
    n = c.count()
    if n > c.max_programs:
        out.append(Finding(
            "executable_census", ERROR, spec.name,
            f"census of {n} reachable programs exceeds the declared "
            f"bound of {c.max_programs} "
            f"(axes: {({k: c.axis_count(v) for k, v in c.axes.items()})})",
            {"count": n, "max_programs": c.max_programs}))
    out.append(Finding(
        "executable_census", INFO, spec.name,
        f"{n} reachable jit program(s) "
        f"(axes: {({k: c.axis_count(v) for k, v in c.axes.items()})}, "
        f"bound {c.max_programs})",
        {"count": n, "max_programs": c.max_programs}))
    return out


RULES: Dict[str, Callable] = {
    "no_host_sync": rule_no_host_sync,
    "donation_honored": rule_donation_honored,
    "collective_divergence": rule_collective_divergence,
    "traffic_budget": rule_traffic_budget,
    "executable_census": rule_executable_census,
}


def run_rules(spec: EntrySpec, rules: Optional[Sequence[str]] = None):
    """Run ``rules`` (default: the entry's own list, else all) against
    one entry point. Returns the findings list (possibly empty)."""
    names = rules or spec.rules or tuple(RULES)
    out = []
    for name in names:
        out += RULES[name](spec)
    return out
