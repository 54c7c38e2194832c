"""qt-prof — per-stage time attribution, machine probing, and roofline
efficiency for every registered hot path.

The observability triad's attribution leg: qt-verify (``analysis``)
proves the performance contract *statically*, the telemetry hub
(``telemetry``) watches runtime health — and this module answers the
question neither can: **where does a step's time go, and how far from
the hardware's limits does each stage run?**

Everything here runs OFF the hot path, as a separate profile pass:

- :class:`StageProfiler` times each registered entry point's jitted
  program (and each census lattice point, so shed variants are
  attributed too) with best-of-N ``block_until_ready`` timing —
  donation-safe (donated buffers are copied fresh per call, so
  profiling never invalidates a live train state);
- :func:`machine_probe` measures what THIS box actually delivers —
  achieved memcpy, random-gather and host<->device bandwidth — one
  shot, a few hundred ms;
- the analytic cost model (``analysis.costmodel``, computed on the
  SAME shared trace qt-verify walks) supplies modeled bytes per stage,
  so every stage gets a roofline efficiency:
  ``modeled_bytes / measured_time / probed_rate``.

Because the profiler is a separate pass over the same compiled
programs, every hot-path invariant (zero per-step host syncs,
bit-identity, flat executable cache) holds by construction: nothing
here is imported by, or hooks into, a jitted program
(tests/test_profile.py pins the host-sync claim with this module
imported; ``scripts/check_leak.py`` phase 10 pins the flat cache).

Results land as ``profile``-kind JSONL records through the shared
``MetricsSink`` schema and, when a :class:`~quiver_tpu.telemetry.
TelemetryHub` is attached, as ``stage_share:<entry>/<stage>`` /
``stage_ms:<entry>/<stage>`` series points — where the hub's default
``stage_share:*`` drift watch turns a stage silently growing its share
of the step into an ``anomaly`` record. ``scripts/qt_prof.py`` is the
CLI; ``scripts/qt_top.py`` renders the latest record per (entry,
stage).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np

import jax
import jax.numpy as jnp

from .analysis.costmodel import CostModel, cost_of, cost_of_fn

#: series-name prefixes the profiler feeds into a TelemetryHub, plus
#: the bench's efficiency figure — ``scripts/lint.sh`` pins that each
#: has a backticked row in docs/observability.md
PROFILE_SERIES = ("stage_share", "stage_ms", "gather_efficiency")


# ---------------------------------------------------------------------------
# the machine probe
# ---------------------------------------------------------------------------


def _best_of(fn, reps: int) -> float:
    fn()                                   # warmup (compile + caches)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def machine_probe(quick: bool = False, reps: int = 3,
                  size_mb: Optional[int] = None) -> Dict[str, float]:
    """One-shot measurement of what small jitted programs achieve on
    the backend this process runs on (``platform`` in the result):
    memcpy GB/s, random-gather GB/s (the tiered lookup's access
    pattern), and host->device / device->host transfer GB/s. They are
    the denominators of the profiler's "% of probe" column — a
    reference taken on this machine on this day, NOT the peak of any
    device: nothing here knows a rate for a ``device_kind``.

    ``quick`` shrinks the working set (8 MB vs 64 MB) and the rep
    count; both sizes comfortably exceed cache on the bench boxes, so
    the numbers read as memory-system bandwidth, not L2."""
    mb = size_mb if size_mb is not None else (8 if quick else 64)
    reps = max(1, reps if not quick else min(reps, 2))
    n = mb * (1 << 20) // 4
    x = jnp.ones((n,), jnp.float32)
    jax.block_until_ready(x)

    copy = jax.jit(lambda a: a + 0.0)      # read n + write n floats
    t = _best_of(lambda: jax.block_until_ready(copy(x)), reps)
    memcpy_gbps = 2 * n * 4 / t / 1e9

    width = 32                             # a narrow feature row
    rows = n // width
    table = x.reshape(rows, width)
    ids = jax.random.randint(jax.random.key(0), (rows,), 0, rows,
                             dtype=jnp.int32)
    jax.block_until_ready(ids)
    gather = jax.jit(lambda tbl, i: tbl[i])
    t = _best_of(lambda: jax.block_until_ready(gather(table, ids)), reps)
    # every row is read once (random order) and written once
    gather_gbps = 2 * rows * width * 4 / t / 1e9

    host = np.ones((n,), np.float32)
    t = _best_of(lambda: jax.block_until_ready(jax.device_put(host)),
                 reps)
    h2d_gbps = n * 4 / t / 1e9
    t = _best_of(lambda: np.asarray(jax.device_get(x)), reps)
    d2h_gbps = n * 4 / t / 1e9

    return {
        "memcpy_gbps": round(memcpy_gbps, 3),
        "gather_gbps": round(gather_gbps, 3),
        "h2d_gbps": round(h2d_gbps, 3),
        "d2h_gbps": round(d2h_gbps, 3),
        "size_mb": mb,
        "platform": jax.default_backend(),
    }


# ---------------------------------------------------------------------------
# stages and groups
# ---------------------------------------------------------------------------


@dataclass
class ProfileStage:
    """One timeable program: a registry spec, a census lattice point,
    or a pipeline sub-stage."""

    name: str
    fn: object
    args: tuple = ()
    donate_argnums: tuple = ()
    cost: Optional[CostModel] = None


@dataclass
class ProfileGroup:
    """Stages profiled and attributed together (one ``profile`` JSONL
    record). ``ref_stage`` names the stage whose time is the share
    denominator — the pipeline group uses its full fused step, so
    "share" reads as "fraction of the step"; without it, shares are of
    the group's total profiled time (the serve ladder, the census
    arities)."""

    name: str
    stages: List[ProfileStage] = field(default_factory=list)
    ref_stage: Optional[str] = None


def _is_key_array(x) -> bool:
    try:
        return jnp.issubdtype(x.dtype, jax.dtypes.prng_key)
    except Exception:
        return False


def _copy_leaf(x):
    """A genuinely fresh buffer for a donated leaf (typed PRNG keys
    can't go through ``jnp.array``)."""
    if not isinstance(x, jax.Array):
        return x
    if _is_key_array(x):
        return jax.random.wrap_key_data(
            jnp.array(jax.random.key_data(x), copy=True))
    return jnp.array(x, copy=True)


class StageProfiler:
    """Best-of-N wall-clock attribution over profile groups.

    Build the groups ONCE (``add_registry`` / ``add_pipeline`` /
    ``add_group``) and call :meth:`run` per profile pass: the jitted
    programs compile on the first pass and are re-timed — never
    re-built — on every later one, which is what lets
    ``check_leak.py`` phase 10 pin a full pass at zero new executables
    and what makes repeated passes honest drift input for the hub.

    ``sink`` receives one ``profile`` JSONL record per group (plus one
    ``__machine__`` record carrying the probe); ``hub`` receives
    ``stage_share:<group>/<stage>`` and ``stage_ms:<group>/<stage>``
    series points per pass, where the default ``stage_share:*`` watch
    raises an anomaly when a stage's share drifts up."""

    def __init__(self, reps: int = 3, probe: Optional[dict] = None,
                 sink=None, hub=None):
        self.reps = max(1, int(reps))
        self.probe = probe
        self.sink = sink
        self.hub = hub
        self.groups: List[ProfileGroup] = []

    # -- building ------------------------------------------------------------
    def add_group(self, group: ProfileGroup) -> "StageProfiler":
        self.groups.append(group)
        return self

    def add_registry(self, names: Optional[Sequence[str]] = None,
                     quick: bool = False) -> "StageProfiler":
        """One group per registered entry point; every spec the
        builder returns (each census lattice point — the serve
        ladder's shed variants, the rows arities) becomes a stage, so
        attribution covers the programs production can actually
        reach."""
        from .analysis.registry import build_entry_specs, entry_names
        for name in (names or entry_names(quick=quick)):
            stages = []
            for spec in build_entry_specs(name):
                # registry fns that are plain closures (tracing needs
                # no jit) would time as op-by-op eager dispatch —
                # hundreds of ms of pure overhead at these shapes; the
                # production path runs them jitted, so time them jitted
                fn = (spec.fn if hasattr(spec.fn, "_cache_size")
                      else jax.jit(spec.fn))
                stages.append(ProfileStage(
                    name=spec.name, fn=fn, args=spec.args,
                    donate_argnums=tuple(spec.donate_argnums),
                    cost=cost_of(spec)))
            self.add_group(ProfileGroup(name=name, stages=stages))
        return self

    def add_pipeline(self) -> "StageProfiler":
        """The canonical hot path decomposed: ``sample`` (the multihop
        walk alone), ``gather`` (the frontier feature gather alone),
        and ``step`` (the fused production train step — the share
        denominator). The gap between sample+gather and the step is
        fusion headroom in time; the gather stage's
        ``gather_index_bytes`` is the same headroom in bytes (the
        frontier-id round trip ROADMAP frontier 2's fused kernel
        deletes). A fourth stage, ``fused_hop``, times the registry's
        single-kernel Pallas sample+gather hop (``fused_hot_hop`` —
        one hop at its own fixture shape, so compare its COST model
        line, ``gather_index_bytes=0``, rather than its wall time
        against the two-hop stages). A fifth, ``fused_multihop``,
        times the registry's full fused walk (qt-fuse-deep — the
        sample+gather front-end the fused train step runs; same
        cost-model reading, ``gather_index_bytes=0`` across ALL
        hops)."""
        from .analysis.registry import _fixture, build_entry_specs
        from .ops.sample_multihop import sample_multihop
        from .parallel.frontier import masked_feature_gather
        fx = _fixture()
        sizes = fx.sizes

        sample_fn = jax.jit(
            lambda ip, ix, s, k: sample_multihop(ip, ix, s, sizes, k))
        sample_args = (fx.indptr, fx.indices, fx.seeds,
                       jax.random.key(7))
        n_id, _ = sample_fn(*sample_args)
        gather_fn = jax.jit(masked_feature_gather)
        gather_args = (fx.feat, n_id)
        step = build_entry_specs("train_step")[0]
        stages = [
            ProfileStage("sample", sample_fn, sample_args,
                         cost=cost_of_fn(sample_fn, sample_args)),
            ProfileStage("gather", gather_fn, gather_args,
                         cost=cost_of_fn(gather_fn, gather_args)),
            ProfileStage("step", step.fn, step.args,
                         donate_argnums=tuple(step.donate_argnums),
                         cost=cost_of(step)),
        ]
        for stage_name, entry in (("fused_hop", "fused_hot_hop"),
                                  ("fused_multihop", "fused_multihop")):
            fused = build_entry_specs(entry)[0]
            stages.append(ProfileStage(
                stage_name,
                fused.fn if hasattr(fused.fn, "_cache_size")
                else jax.jit(fused.fn),
                fused.args, cost=cost_of(fused)))
        return self.add_group(ProfileGroup("train_pipeline", stages,
                                           ref_stage="step"))

    @property
    def jitted_fns(self) -> List:
        """Every stage fn with an executable cache — what check_leak
        watches for flatness across profile passes."""
        return [st.fn for g in self.groups for st in g.stages
                if hasattr(st.fn, "_cache_size")]

    # -- timing --------------------------------------------------------------
    def _fresh_args(self, stage: ProfileStage) -> tuple:
        if not stage.donate_argnums:
            return stage.args
        donate = set(stage.donate_argnums)
        return tuple(
            jax.tree_util.tree_map(_copy_leaf, a) if i in donate else a
            for i, a in enumerate(stage.args))

    def _time_stage(self, stage: ProfileStage):
        """(best_s, mean_s) over ``reps`` timed calls after one warmup
        call; donated args are copied OUTSIDE the timed region, fresh
        JUST BEFORE each call (one transient copy live at a time — a
        big donated train state must not sit in device memory reps+1
        times over), so the entry's real (donating) program is what
        runs and the fixture's live buffers survive the pass."""
        jax.block_until_ready(stage.fn(*self._fresh_args(stage)))
        times = []
        for _ in range(self.reps):
            args = self._fresh_args(stage)
            t0 = time.perf_counter()
            jax.block_until_ready(stage.fn(*args))
            times.append(time.perf_counter() - t0)
            del args
        return min(times), sum(times) / len(times)

    def _probe_for(self, cost: Optional[CostModel]):
        """The probed rate a stage is set against: the random-gather
        figure when gathers dominate its modeled traffic, memcpy
        otherwise."""
        if cost is None or self.probe is None:
            return None, None
        total = max(cost.total_bytes, 1)
        key = ("gather_gbps"
               if cost.gather_bytes + cost.gather_index_bytes
               >= total // 2 else "memcpy_gbps")
        return key, self.probe.get(key)

    # -- the pass ------------------------------------------------------------
    def run(self) -> List[dict]:
        """One profile pass: time every stage of every group, attach
        the modeled bytes + roofline efficiency, emit/feed, and return
        the ``profile`` records (one per group; a ``__machine__``
        record carries the probe when one was taken)."""
        records: List[dict] = []
        if self.probe is not None:
            records.append({"entry": "__machine__",
                            "machine": dict(self.probe)})
        for group in self.groups:
            timed = [(st, *self._time_stage(st)) for st in group.stages]
            ref_ms = None
            if group.ref_stage is not None:
                for st, _, mean_s in timed:
                    if st.name == group.ref_stage:
                        ref_ms = mean_s * 1e3
            if ref_ms is None:
                ref_ms = sum(mean_s for _, _, mean_s in timed) * 1e3
            stages = []
            for st, best_s, mean_s in timed:
                row = {
                    "stage": st.name,
                    "mean_ms": round(mean_s * 1e3, 4),
                    "best_ms": round(best_s * 1e3, 4),
                    "reps": self.reps,
                    "share": round(mean_s * 1e3 / ref_ms, 4)
                    if ref_ms else None,
                }
                if st.cost is not None:
                    row["modeled"] = st.cost.record()
                    achieved = st.cost.total_bytes / best_s / 1e9
                    row["achieved_gbps"] = round(achieved, 3)
                    probe_key, probed = self._probe_for(st.cost)
                    if probed:
                        row["probe"] = probe_key
                        row["efficiency"] = round(achieved / probed, 4)
                stages.append(row)
            records.append({"entry": group.name, "stages": stages,
                            "step_ms": round(ref_ms, 4),
                            "ref_stage": group.ref_stage})
        self._publish(records)
        return records

    def _publish(self, records: List[dict]) -> None:
        if self.sink is not None:
            for rec in records:
                self.sink.emit(rec, kind="profile")
        if self.hub is not None:
            for rec in records:
                entry = rec.get("entry", "")
                if entry.startswith("__"):
                    continue
                for st in rec.get("stages", ()):
                    tag = f"{entry}/{st['stage']}"
                    self.hub.observe(f"stage_share:{tag}", st.get("share"))
                    self.hub.observe(f"stage_ms:{tag}", st.get("mean_ms"))


def render_records(records: List[dict], color: bool = False) -> str:
    """The CLI table: one line per stage —
    ``stage | mean ms | modeled bytes | achieved GB/s | % of probe
    | % of step`` (shared by ``scripts/qt_prof.py`` and tests)."""
    GREEN, YELLOW, RED, DIM, RESET = ("\x1b[32m", "\x1b[33m",
                                      "\x1b[31m", "\x1b[2m", "\x1b[0m")

    def tint(code, s):
        return f"{code}{s}{RESET}" if color else s

    lines = []
    for rec in records:
        if rec.get("entry") == "__machine__":
            m = rec["machine"]
            lines.append(tint(DIM, (
                f"machine probe ({m.get('platform', '?')}, "
                f"{m.get('size_mb')} MB): "
                f"memcpy {m['memcpy_gbps']:.2f} GB/s, "
                f"gather {m['gather_gbps']:.2f} GB/s, "
                f"h2d {m['h2d_gbps']:.2f} GB/s, "
                f"d2h {m['d2h_gbps']:.2f} GB/s")))
            continue
        lines.append(f"{rec['entry']}  "
                     f"(step {rec.get('step_ms', 0):.3f} ms)")
        for st in rec.get("stages", ()):
            mod = st.get("modeled") or {}
            eff = st.get("efficiency")
            eff_s = "   n/a" if eff is None else f"{100 * eff:5.1f}%"
            if eff is not None:
                eff_s = tint(GREEN if eff >= 0.5 else
                             YELLOW if eff >= 0.15 else RED, eff_s)
            share = st.get("share")
            share_s = ("  n/a " if share is None
                       else f"{100 * share:5.1f}%")
            lines.append(
                f"  {st['stage']:<24} {st['mean_ms']:>9.3f} ms  "
                f"{mod.get('total_bytes', 0):>12,} B  "
                f"{st.get('achieved_gbps', 0.0):>8.3f} GB/s  "
                f"{eff_s} of probe  {share_s} of step")
    return "\n".join(lines)
